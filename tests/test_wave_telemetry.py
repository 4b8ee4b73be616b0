"""What the fused checker tells about its own waves, and what it costs.

- **Stage scopes**: every stage of the wave program runs under a
  ``jax.named_scope`` whose name lands in the compiled dispatch's op
  metadata, where a device trace's reader finds it
  (``benchmark/trace_stages.py``).
- **Loop counters**: each dispatch counts the rounds of its table-probe
  and local-dedup loops; the counts ride the stats vector into every
  ``dispatch_log`` entry, and repeat exactly from run to run.
- **Host spans**: the host loop's launch, processing and stats wait are
  profiler annotations, on the host plane of a profiler session; with
  no session and ``STpu_TRACE`` unset they record nothing.
- **The sharded-fused engine** names the same stages plus ``exchange``,
  counts its slowest shard's rounds, its exchange rounds and the rows it
  sends between shards, and opens the same spans, with results
  unchanged.
"""

import glob
import os
import re
import sys

import pytest

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "examples"))

from stateright_tpu.obs import NULL_TRACER  # noqa: E402
from stateright_tpu.tpu.engine import probe_chunk  # noqa: E402
from stateright_tpu.tpu.hashing import SENTINEL  # noqa: E402
from stateright_tpu.tpu.sharded_fused import (  # noqa: E402
    exchange_bucket_rows)
from two_phase_commit import TwoPhaseSys  # noqa: E402

STAGES = ("load", "properties", "expand", "fingerprint", "local_dedup",
          "probe", "store")


def _check(batch_size=8):
    return TwoPhaseSys(3).checker().spawn_tpu_bfs(
        batch_size=batch_size, fused=True).join()


@pytest.fixture(scope="module")
def dispatch_scopes():
    """The name-scope components of every op of a fused 2pc-3
    dispatch program, from its compiled HLO's metadata."""
    c = _check()
    programs = [p for k, p in c._wave_cache.items() if k[0] == "dispatch"]
    assert programs
    parts = set()
    for prog in programs:
        for path in re.findall(r'op_name="([^"]*)"', prog.as_text()):
            parts.update(path.split("/"))
    return parts


@pytest.mark.parametrize("scope", STAGES)
def test_dispatch_ops_carry_stage_scope(dispatch_scopes, scope):
    assert scope in dispatch_scopes


def test_dispatch_counts_loop_rounds():
    c = _check()
    assert c.unique_state_count() == 288
    entries = [e for e in c.dispatch_log if e["waves"]]
    assert entries
    for e in entries:
        for key in ("probe_rounds", "dedup_rounds"):
            assert 1 <= e[key] <= e["candidates"], (key, e)
        # every probe round carries one chunk of the wave's rows
        assert e["probe_slots"] == e["probe_rounds"] * probe_chunk(
            e["bucket"] * c._F), e
        assert e["candidates"] <= e["probe_slots"], e
    # a no-op dispatch launched past a rest point ran no loop
    assert all(e["probe_rounds"] == e["dedup_rounds"] == e["probe_slots"]
               == 0 for e in c.dispatch_log if not e["waves"])
    assert all(e["host_s"] >= 0 for e in c.dispatch_log)


def test_loop_rounds_repeat_exactly():
    def rounds(c):
        return [(e["waves"], e["probe_rounds"], e["dedup_rounds"])
                for e in c.dispatch_log]

    assert rounds(_check()) == rounds(_check())


def test_host_spans_on_the_profiler_host_plane(tmp_path):
    import jax
    from jax.profiler import ProfileData

    _check()  # compiles outside the session
    jax.profiler.start_trace(str(tmp_path))
    try:
        _check()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names.update(ev.name for ev in line.events
                             if ev.name.startswith("fused."))
    assert {"fused.launch", "fused.process", "fused.stats_wait"} <= names


def _mesh_check(**kw):
    import jax
    import numpy as np
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:4]), ("shard",))
    return TwoPhaseSys(4).checker().spawn_tpu_bfs(
        **dict(dict(fused=True, mesh=mesh, batch_size=16), **kw))


@pytest.fixture(scope="module")
def mesh_pair():
    """A sharded-fused 2pc-4 check on four devices and the classic
    sharded engine's on the same mesh."""
    fused = _mesh_check().join()
    classic = _mesh_check(fused=False).join()
    return fused, classic


@pytest.mark.parametrize("scope", STAGES + ("exchange",))
def test_sharded_dispatch_ops_carry_stage_scope(mesh_pair, scope):
    c, _ = mesh_pair
    programs = [p for k, p in c._wave_cache.items()
                if k[0] == "sharded-dispatch"]
    assert programs
    parts = {part for prog in programs
             for path in re.findall(r'op_name="([^"]*)"', prog.as_text())
             for part in path.split("/")}
    assert scope in parts


def test_sharded_dispatch_counts_rounds_and_exchange(mesh_pair):
    c, _ = mesh_pair
    assert c.unique_state_count() == 1568
    n = 4
    entries = [e for e in c.dispatch_log if e["waves"]]
    assert entries
    for e in entries:
        # a wave waits for its slowest shard: at least one round each
        for key in ("probe_rounds", "dedup_rounds"):
            assert e["waves"] <= e[key] <= e["candidates"], (key, e)
        # every wave runs at least one exchange round, at most n
        assert e["waves"] <= e["exchange_rounds"] <= n * e["waves"], e
        # each round carries n-1 off-shard buckets of CAP rows a shard
        cap = exchange_bucket_rows(e["bucket"] * c._F, n)
        assert e["exchange_slots"] == (e["exchange_rounds"] * n * (n - 1)
                                       * cap)
        # a shard sends at most its B*F successors a wave, each in a slot
        assert 0 < e["exchange_rows"] <= e["exchange_slots"]
        assert e["exchange_rows"] <= e["waves"] * n * e["bucket"] * c._F
        assert e["exchange_rows"] <= e["candidates"]
        # the slowest shard's rounds, each over a chunk of the n*CAP
        # rows it receives in an exchange round
        assert e["probe_slots"] == e["probe_rounds"] * probe_chunk(
            n * cap), e
    assert all(e["probe_rounds"] == e["dedup_rounds"] == e["probe_slots"]
               == e["exchange_rows"] == e["exchange_slots"]
               == e["exchange_rounds"] == 0
               for e in c.dispatch_log if not e["waves"])
    assert all(e["host_s"] >= 0 for e in c.dispatch_log)


def test_sharded_counts_leave_the_results_as_the_classic_engine(mesh_pair):
    fused, classic = mesh_pair
    assert fused.unique_state_count() == classic.unique_state_count()
    assert fused.state_count() == classic.state_count()
    assert set(fused.discoveries()) == set(classic.discoveries())
    for name in fused.discoveries():
        assert (fused.discovery(name).encode()
                == classic.discovery(name).encode())


def test_sharded_arena_rows_are_the_admitted_states(mesh_pair):
    c, _ = mesh_pair
    shards = c.arena_rows()
    assert len(shards) == 4
    fps = [int(f) for s in shards for f in s["fps"]]
    assert len(fps) == len(set(fps)) == c.unique_state_count()
    # every shard holds its own fingerprints, all expanded at the end
    for i, s in enumerate(shards):
        assert all(int(f) % 4 == i for f in s["fps"])
        assert s["head"] == len(s["fps"]) == len(s["lanes"])
    parents = {int(p) for s in shards for p in s["parents"]}
    assert parents - set(fps) == {int(SENTINEL)}


def test_fused_arena_rows_unpack_to_the_admitted_states():
    """On one chip, from packed storage rows: each row's lanes hash to
    its own fingerprint, every admitted state once."""
    import numpy as np

    from stateright_tpu.tpu.hashing import host_fp64

    c = TwoPhaseSys(3).checker().spawn_tpu_bfs(
        fused=True, batch_size=8, pack_arena=True).join()
    assert c._pack_on
    (rows,) = c.arena_rows()
    assert rows["head"] == len(rows["fps"]) == c.unique_state_count()
    assert [host_fp64(np.asarray(v)) for v in rows["lanes"]] == [
        int(f) for f in rows["fps"]]


def test_sharded_preempt_stops_at_a_dispatch_boundary():
    import time

    c = _mesh_check(batch_size=4, waves_per_dispatch=2)
    while not c.dispatch_log:
        time.sleep(0.01)
    c.preempt()
    c.join()
    assert c.preempted
    heads = sum(s["head"] for s in c.arena_rows())
    assert heads == sum(e["rows"] for e in c.dispatch_log)
    assert c.unique_state_count() < 1568


def test_sharded_host_spans_on_the_profiler_host_plane(tmp_path):
    import jax
    from jax.profiler import ProfileData

    _mesh_check().join()  # compiles outside the session
    jax.profiler.start_trace(str(tmp_path))
    try:
        _mesh_check().join()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names.update(ev.name for ev in line.events
                             if ev.name.startswith("fused."))
    assert {"fused.launch", "fused.process", "fused.stats_wait",
            "fused.parent_sync"} <= names


def test_no_session_no_trace_writes_nothing(tmp_path, monkeypatch):
    monkeypatch.delenv("STpu_TRACE", raising=False)
    monkeypatch.chdir(tmp_path)
    c = _check()
    assert c._tracer is NULL_TRACER
    assert c.unique_state_count() == 288
    assert os.listdir(tmp_path) == []
