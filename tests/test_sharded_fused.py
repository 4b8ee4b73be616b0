"""The fused multi-chip engine (`stateright_tpu/tpu/sharded_fused.py`).

The sharded paths of the device battery exercise it implicitly (it is
the ``spawn_tpu_bfs(sharded=True)`` default); these pin its specifics:
discovery identity vs the classic sharded engine, on-device growth of
the per-shard tables/arenas, checkpoint round-trips, and ABD parity.
"""

import pytest
import os
import sys

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "examples"))

from stateright_tpu.tpu.engine import append_chunk
from stateright_tpu.tpu.sharded_fused import (ShardedFusedTpuBfsChecker,
                                              exchange_bucket_rows,
                                              exchange_window_rows)
from stateright_tpu.tpu.sharded import ShardedTpuBfsChecker
from two_phase_commit import TwoPhaseSys


def test_spawn_sharded_selects_fused_by_default():
    c = (TwoPhaseSys(3).checker()
         .spawn_tpu_bfs(sharded=True, batch_size=16).join())
    assert isinstance(c, ShardedFusedTpuBfsChecker)
    assert c.unique_state_count() == 288


def test_matches_classic_sharded_engine_bit_for_bit():
    model = TwoPhaseSys(4)
    classic = model.checker().spawn_tpu_bfs(
        sharded=True, batch_size=32, fused=False).join()
    fused = model.checker().spawn_tpu_bfs(
        sharded=True, batch_size=32).join()
    assert isinstance(classic, ShardedTpuBfsChecker)
    assert not isinstance(classic, ShardedFusedTpuBfsChecker)
    # every wave's buckets fit one exchange round: the owners receive
    # the rows in the classic engine's shard-major order
    assert all(e["exchange_rounds"] == e["waves"]
               for e in fused.dispatch_log)
    assert fused.unique_state_count() == classic.unique_state_count()
    assert fused.state_count() == classic.state_count()
    assert set(fused.discoveries()) == set(classic.discoveries())
    for name in fused.discoveries():
        assert (fused.discovery(name).encode()
                == classic.discovery(name).encode())


@pytest.mark.parametrize("rows,shards", [
    (4096 * 57, 4), (8 * 22, 4), (32 * 14, 8), (100, 3)])
def test_exchange_buckets_and_window(rows, shards):
    """A bucket is the balanced share, a multiple of 8, so one round
    carries evenly spread rows and a wave takes at most ``shards``
    rounds. Round r appends what it admitted of its ``shards * CAP``
    received rows in whole append chunks, after at most ``shards *
    min(rows, r * CAP)`` rows admitted before it: the window a wave may
    write is the worst of those, ``shards * rows`` only where CAP
    divides ``rows`` and the chunk divides ``shards * CAP``."""
    cap = exchange_bucket_rows(rows, shards)
    assert cap % 8 == 0 and rows <= shards * cap < rows + 8 * shards
    rounds = -(-rows // cap)
    assert rounds <= shards
    chunk = append_chunk(shards * cap)
    round_window = -(-shards * cap // chunk) * chunk
    worst = max(shards * min(rows, r * cap) + round_window
                for r in range(rounds))
    assert exchange_window_rows(rows, shards) == worst >= shards * rows
    assert (worst == shards * rows) == (
        rows % cap == 0 and round_window == shards * cap)


def test_waves_of_several_exchange_rounds(monkeypatch):
    """Buckets of 8 rows send a wave's successors home in several
    rounds, each deduplicated, probed and appended by its owner: the
    admitted states, the counts and the discoveries stay the host
    checker's, and every discovery path replays."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from stateright_tpu.tpu import sharded_fused

    monkeypatch.setattr(sharded_fused, "exchange_bucket_rows",
                        lambda rows, shards: 8)
    model = TwoPhaseSys(4)
    host = model.checker().spawn_bfs().join()
    c = model.checker().spawn_tpu_bfs(
        fused=True, batch_size=32,
        mesh=Mesh(np.array(jax.devices()[:4]), ("shard",))).join()
    log = [e for e in c.dispatch_log if e["waves"]]
    assert (sum(e["exchange_rounds"] for e in log)
            > sum(e["waves"] for e in log))
    assert c.unique_state_count() == host.unique_state_count()
    assert c.state_count() == host.state_count()
    assert set(c.discoveries()) == set(host.discoveries())
    for name, path in c.discoveries().items():
        assert model.property(name).condition(model, path.last_state())


def test_on_device_growth_paths():
    model = TwoPhaseSys(4)
    ref = model.checker().spawn_bfs().join()
    grown = model.checker().spawn_tpu_bfs(
        sharded=True, batch_size=8, table_capacity=1 << 12,
        arena_capacity=1 << 10, waves_per_dispatch=2).join()
    assert grown.unique_state_count() == ref.unique_state_count()
    assert set(grown.discoveries()) == set(ref.discoveries())


def test_checkpoint_crosses_into_single_device_engine(tmp_path):
    """A sharded-fused snapshot resumes on the single-device fused
    engine (and back): ownership/table layout are rebuilt from data."""
    model = TwoPhaseSys(4)
    full = model.checker().spawn_bfs().join()

    ckpt = str(tmp_path / "shf.npz")
    model.checker().target_state_count(400).spawn_tpu_bfs(
        sharded=True, batch_size=32, checkpoint_path=ckpt).join()
    resumed = model.checker().spawn_tpu_bfs(
        batch_size=64, resume_from=ckpt).join()
    assert resumed.unique_state_count() == full.unique_state_count()
    assert set(resumed.discoveries()) == set(full.discoveries())

    ckpt2 = str(tmp_path / "single.npz")
    model.checker().target_state_count(400).spawn_tpu_bfs(
        batch_size=64, checkpoint_path=ckpt2).join()
    resumed2 = model.checker().spawn_tpu_bfs(
        sharded=True, batch_size=32, resume_from=ckpt2).join()
    assert resumed2.unique_state_count() == full.unique_state_count()
    assert set(resumed2.discoveries()) == set(full.discoveries())


@pytest.mark.slow  # ~11s; single-device symmetry parity stays in
# the fast set, the sharded pair's symmetry rides here
def test_symmetry_on_sharded_engines():
    """Symmetry reduction composes with sharding: dedup (and therefore
    ownership) keys on the representative's fingerprint while paths keep
    original-state fingerprints (the dfs.rs:258-267 rule).

    Because the 2pc device representative is an EXACT canonical form,
    the quotient size is the true orbit count — 314 at 5 RMs —
    independent of wave composition, so the sharded engines count
    identically to the single-device ones. (The reference's value-only
    sort is order-dependent: 665 under its DFS, `2pc.rs:138`.)"""
    for fused in (True, False):
        c = (TwoPhaseSys(5).checker().symmetry()
             .spawn_tpu_bfs(sharded=True, batch_size=32,
                            fused=fused).join())
        assert c.unique_state_count() == 314, fused
        assert set(c.discoveries()) == {"abort agreement",
                                        "commit agreement"}, fused


def test_abd_sharded_fused_544():
    """The linearizable-register parity gate on the fused multi-chip
    path (`examples/linearizable-register.rs:256`)."""
    from linearizable_register import AbdModelCfg

    model = AbdModelCfg(2, 2).into_model()
    c = model.checker().spawn_tpu_bfs(sharded=True, batch_size=64).join()
    assert c.unique_state_count() == 544
    assert set(c.discoveries()) == {"value chosen"}
    c.assert_properties()
