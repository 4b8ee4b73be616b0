"""The adaptive wave scheduler (engine.py / fused.py / sharded*.py).

Three contracts:

- **Cross-B parity**: counts, discoveries, parent pointers, and
  checkpoints are identical whatever dispatch width the scheduler picks
  — the bucket ladder is purely a performance schedule. Pinned across
  all four device engines on 2pc and paxos.
- **Donation**: table growth / rehash never retains the pre-growth
  buffer (the arena doubling stops doubling peak memory).
- **Telemetry**: dispatch_log / scheduler_stats report the ladder, the
  buckets actually used, and the pipeline depth achieved — bench.py's
  steady-rate and BENCH attribution depend on them.
"""

import os
import sys

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "examples"))

import numpy as np
import pytest

from stateright_tpu.tpu.engine import batch_bucket_ladder, pick_bucket
from two_phase_commit import TwoPhaseSys


def _spawn(model, engine, B, **kwargs):
    b = model.checker()
    if engine == "fused":
        return b.spawn_tpu_bfs(batch_size=B, fused=True, **kwargs)
    if engine == "classic":
        return b.spawn_tpu_bfs(batch_size=B, fused=False, **kwargs)
    if engine == "sharded-fused":
        return b.spawn_tpu_bfs(batch_size=B, sharded=True, **kwargs)
    assert engine == "sharded-classic"
    return b.spawn_tpu_bfs(batch_size=B, sharded=True, fused=False,
                           **kwargs)


def test_bucket_ladder_shape():
    assert batch_bucket_ladder(1024, None) == (1024,)
    assert batch_bucket_ladder(1024, 1024) == (1024,)
    assert batch_bucket_ladder(1024, 16384) == (
        1024, 2048, 4096, 8192, 16384)
    # Non-power-of-two top rounds up; base is kept verbatim.
    assert batch_bucket_ladder(64, 200) == (64, 128, 256)
    assert pick_bucket((64, 128, 256), 1) == 64
    assert pick_bucket((64, 128, 256), 65) == 128
    assert pick_bucket((64, 128, 256), 10 ** 9) == 256


@pytest.mark.parametrize("engine", [
    "fused",
    # The sharded pair compiles three shard_map programs each (~85s of
    # the tier-1 budget); round 15 moved the classic arm out too (the
    # fused arm is the fast-set representative; cross-B independence is
    # engine-generic — the dedup rule, not the host loop).
    pytest.param("classic", marks=pytest.mark.slow),
    pytest.param("sharded-fused", marks=pytest.mark.slow),
    pytest.param("sharded-classic", marks=pytest.mark.slow)])
def test_cross_batch_parity_2pc(engine):
    """Same model at three batch buckets: identical unique counts,
    total counts, and discovery identities (B-independence is what
    makes the adaptive ladder safe)."""
    model = TwoPhaseSys(4)
    ref = model.checker().spawn_bfs().join()
    for B in (32, 128, 512):
        c = _spawn(model, engine, B).join()
        assert c.unique_state_count() == ref.unique_state_count(), \
            (engine, B)
        assert c.state_count() == ref.state_count(), (engine, B)
        assert set(c.discoveries()) == set(ref.discoveries()), (engine, B)


@pytest.mark.slow  # the 2pc parity above is the fast-set gate; the
# paxos workload re-runs the same matrix at ~40s (tier-1 headroom)
@pytest.mark.parametrize("engine", ["fused", "classic"])
def test_cross_batch_parity_paxos(engine):
    from paxos import PaxosModelCfg

    model = PaxosModelCfg(1, 3).into_model()
    results = []
    for B in (64, 512):
        c = _spawn(model, engine, B).join()
        results.append((c.unique_state_count(), c.state_count(),
                        frozenset(c.discoveries())))
    assert results[0] == results[1]


def test_adaptive_ladder_matches_fixed_batch():
    """A run under the adaptive scheduler (multi-rung ladder, several
    buckets actually exercised) is bit-identical to the fixed-width
    run, and the telemetry shows the ladder was used."""
    model = TwoPhaseSys(4)
    ref = model.checker().spawn_tpu_bfs(batch_size=256).join()
    c = model.checker().spawn_tpu_bfs(
        batch_size=16, max_batch_size=256, waves_per_dispatch=2).join()
    assert c.unique_state_count() == ref.unique_state_count()
    assert c.state_count() == ref.state_count()
    assert set(c.discoveries()) == set(ref.discoveries())
    stats = c.scheduler_stats()
    assert stats["bucket_ladder"] == [16, 32, 64, 128, 256]
    used = {int(b) for b in stats["bucket_dispatches"]}
    assert used <= set(stats["bucket_ladder"])
    assert len(used) >= 2, "the ladder should actually adapt"
    assert stats["dispatches"] == len(c.dispatch_log)


def _ckpt_payload(path):
    """Every npz member's raw bytes (member-wise, not whole-file: the
    zip container embeds timestamps; the PAYLOAD is what must match)."""
    with np.load(path) as data:
        return {k: data[k].tobytes() for k in sorted(data.files)}


def _succ_knobs(engine, on):
    """The successor-path knobs each engine accepts (ISSUE 2):
    ``succ_ladder`` everywhere (the fused engines accept and ignore it),
    ``exchange_novel_only`` on the sharded pair."""
    kw = {"succ_ladder": on}
    if engine.startswith("sharded"):
        kw["exchange_novel_only"] = on
    return kw


@pytest.mark.parametrize("engine", [
    "fused",
    # round-15 tier-1 budget: one fast representative.
    pytest.param("classic", marks=pytest.mark.slow),
    pytest.param("sharded-fused", marks=pytest.mark.slow),
    pytest.param("sharded-classic", marks=pytest.mark.slow)])
def test_succ_path_opts_bit_identical_2pc(engine, tmp_path):
    """ISSUE 2 acceptance: intra-wave local dedup + successor ladder ON
    vs OFF — counts, discoveries, parent maps, and checkpoint payload
    bytes bit-identical on all four engines (the sharded pair runs on
    the 8-device virtual mesh, covering the novelty-routed exchange's
    discovery parity)."""
    model = TwoPhaseSys(4)
    runs = {}
    for on in (True, False):
        path = str(tmp_path / f"{engine}-{on}.npz")
        c = _spawn(model, engine, 48, checkpoint_path=path,
                   **_succ_knobs(engine, on)).join()
        runs[on] = (c.unique_state_count(), c.state_count(),
                    set(c.discoveries()), dict(c._parent_map()),
                    _ckpt_payload(path))
    assert runs[True][:4] == runs[False][:4], engine
    assert runs[True][4] == runs[False][4], \
        f"{engine}: checkpoint payload bytes differ with succ opts on"


@pytest.mark.slow  # the 2pc matrix above is the fast-set gate; this
# adds the paxos workload for all four engines (tier-1 budget headroom)
@pytest.mark.parametrize("engine", ["fused", "classic",
                                    "sharded-fused", "sharded-classic"])
def test_succ_path_opts_bit_identical_paxos(engine):
    from paxos import PaxosModelCfg

    model = PaxosModelCfg(1, 3).into_model()
    results = []
    for on in (True, False):
        c = _spawn(model, engine, 128, **_succ_knobs(engine, on)).join()
        results.append((c.unique_state_count(), c.state_count(),
                        frozenset(c.discoveries()),
                        dict(c._parent_map())))
    assert results[0] == results[1], engine


def test_scheduler_stats_report_succ_telemetry():
    """bench.py forwards scheduler_stats verbatim, so
    the successor-path keys must be present and self-consistent."""
    c = TwoPhaseSys(4).checker().spawn_tpu_bfs(
        batch_size=64, fused=False).join()
    stats = c.scheduler_stats()
    sl = stats["succ_ladder"]
    assert sl["enabled"] is True
    assert sum(sl["out_rows_dispatches"].values()) == stats["dispatches"]
    ld = stats["local_dedup"]
    assert ld["distinct_candidates"] <= ld["successors"]
    assert 0.0 <= ld["collapse_ratio"] <= 1.0


@pytest.mark.slow  # ~16s; cross-B checkpoint BYTE parity — the
# fast set keeps cross-B count/discovery parity (classic+fused)
def test_checkpoints_identical_across_buckets(tmp_path):
    """End-of-run checkpoints carry the same visited set and the same
    parent map whatever the batch bucket, and a checkpoint written at
    one bucket resumes at another."""
    model = TwoPhaseSys(4)
    snaps = {}
    for B in (32, 256):
        path = str(tmp_path / f"b{B}.npz")
        model.checker().spawn_tpu_bfs(
            batch_size=B, checkpoint_path=path).join()
        with np.load(path) as data:
            snaps[B] = {
                "visited": frozenset(data["visited"].tolist()),
                "parents": dict(zip(data["parent_child"].tolist(),
                                    data["parent_parent"].tolist())),
            }
    assert snaps[32]["visited"] == snaps[256]["visited"]
    assert snaps[32]["parents"] == snaps[256]["parents"]

    # Cross-bucket resume: a mid-run snapshot from B=32 finishes under
    # B=256 with the full-space counts.
    full = model.checker().spawn_bfs().join()
    ckpt = str(tmp_path / "mid.npz")
    model.checker().target_state_count(400).spawn_tpu_bfs(
        batch_size=32, checkpoint_path=ckpt).join()
    resumed = model.checker().spawn_tpu_bfs(
        batch_size=256, resume_from=ckpt).join()
    assert resumed.unique_state_count() == full.unique_state_count()
    assert set(resumed.discoveries()) == set(full.discoveries())


def test_pipelined_dispatches_keep_parity():
    """Depth-3 pipelining with single-wave dispatches (maximum overlap
    pressure): counts identical, and the telemetry proves dispatches
    were actually in flight together."""
    model = TwoPhaseSys(4)
    ref = model.checker().spawn_bfs().join()
    c = model.checker().spawn_tpu_bfs(
        batch_size=64, waves_per_dispatch=1, inflight_dispatches=3,
        fused=True).join()
    assert c.unique_state_count() == ref.unique_state_count()
    assert set(c.discoveries()) == set(ref.discoveries())
    assert c.scheduler_stats()["max_inflight"] >= 2


def test_growth_releases_pre_growth_buffers():
    """The donation regression gate: grow/rehash consume their input —
    the pre-growth arena/table buffer is released, not retained."""
    import jax.numpy as jnp

    from stateright_tpu.tpu.hashing import SENTINEL

    c = TwoPhaseSys(3).checker().spawn_tpu_bfs(
        batch_size=32, fused=True).join()
    rehash = c._rehash_fn(1 << 12, 1 << 13)
    old_table = jnp.full((1 << 12,), jnp.uint64(SENTINEL))
    new_table = rehash(old_table)
    assert old_table.is_deleted(), "rehash retained the old table"
    assert new_table.shape == (1 << 13,)

    grow = c._grow_fn(1 << 10, 1 << 11, jnp.uint32, c._W)
    old_arena = jnp.zeros((1 << 10, c._W), jnp.uint32)
    new_arena = grow(old_arena)
    assert old_arena.is_deleted(), "grow retained the old arena"
    assert new_arena.shape == (1 << 11, c._W)


def test_growth_releases_pre_growth_buffers_sharded():
    import jax.numpy as jnp

    from stateright_tpu.tpu.hashing import SENTINEL

    c = TwoPhaseSys(3).checker().spawn_tpu_bfs(
        batch_size=16, sharded=True).join()
    n = c._n
    rehash = c._rehash_fn(1 << 10, 1 << 11)
    old_table = jnp.full((n << 10,), jnp.uint64(SENTINEL))
    new_table = rehash(old_table)
    assert old_table.is_deleted()
    assert new_table.shape == (n << 11,)


def test_steady_rate_excludes_compile_time():
    """bench._steady_rate subtracts AOT compile spans and drops
    lazily-flagged intervals, so a mid-run bucket compile cannot be
    charged to throughput."""
    import bench

    class Fake:
        wave_log = [(0.0, 0)]
        # 10 s wall, of which 6 s was one AOT compile; 4 s of real work
        # produced 400 states.
        dispatch_log = [
            {"t": 7.0, "states": 100, "bucket": 64, "compiled": False,
             "waves": 1, "inflight": 1},
            {"t": 10.0, "states": 400, "bucket": 128, "compiled": False,
             "waves": 1, "inflight": 1},
        ]
        compile_log = [(6.5, 6.0)]

    assert abs(bench._steady_rate(Fake()) - 100.0) < 1e-6

    class Lazy(Fake):
        compile_log = []
        dispatch_log = [
            {"t": 7.0, "states": 100, "bucket": 64, "compiled": True,
             "waves": 1, "inflight": 1},
            {"t": 10.0, "states": 400, "bucket": 128, "compiled": False,
             "waves": 1, "inflight": 1},
        ]

    assert abs(bench._steady_rate(Lazy()) - 100.0) < 1e-6


def test_parity_gate_runs_device_in_process(monkeypatch):
    """The gate compares the host reference with a device run made in
    this process, on the backend the headline runs on, and records that
    backend."""
    import bench

    class Run:
        def __init__(self, unique):
            self.unique = unique

        def unique_state_count(self):
            return self.unique

        def discoveries(self):
            return {"atomicity": None}

    device = {"unique": 8832}
    monkeypatch.setattr(bench, "RESULT", dict(bench.RESULT))
    monkeypatch.setitem(bench._PARITY, "status", "pending")
    monkeypatch.setattr(bench, "_host_bfs",
                        lambda model, cap=None: (Run(8832), 100.0, 1.0))
    monkeypatch.setattr(bench, "_tpu_bfs", lambda *a, **k: (
        Run(device["unique"]), 123.0, True))
    monkeypatch.setenv("BENCH_PARITY_RMS", "5")
    bench._stage_parity_gate("tpu")
    assert bench._PARITY["status"] == "ok"
    assert bench.RESULT["parity_backend"] == "tpu"
    assert "tpu backend" in bench.RESULT["parity"]
    # Mismatched counts must fail the gate.
    bench._PARITY["status"] = "pending"
    device["unique"] = 8831
    with pytest.raises(AssertionError, match="unique-state mismatch"):
        bench._stage_parity_gate("tpu")


def test_scheduler_stats_occupancy_is_a_stream_view():
    """succ_ladder occupancy recomputes exactly from the dispatch_log
    — a view over the wave-event stream, no parallel bookkeeping (a
    zero-wave no-op dispatch contributes to neither side)."""
    model = TwoPhaseSys(2)
    c = model.checker().spawn_tpu_bfs(batch_size=16, fused=False).join()
    log = c.dispatch_log
    want = (sum(e["rows"] for e in log)
            / sum(e["bucket"] * e["waves"] for e in log))
    assert c.scheduler_stats()["succ_ladder"]["occupancy"] \
        == round(want, 4)
