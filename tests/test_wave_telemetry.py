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
    # a no-op dispatch launched past a rest point ran no loop
    assert all(e["probe_rounds"] == e["dedup_rounds"] == 0
               for e in c.dispatch_log if not e["waves"])
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


def test_no_session_no_trace_writes_nothing(tmp_path, monkeypatch):
    monkeypatch.delenv("STpu_TRACE", raising=False)
    monkeypatch.chdir(tmp_path)
    c = _check()
    assert c._tracer is NULL_TRACER
    assert c.unique_state_count() == 288
    assert os.listdir(tmp_path) == []
