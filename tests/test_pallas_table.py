"""Pallas visited-table kernel vs the XLA probe loop.

The kernel (``tpu/pallas_table.py``) must be bit-identical to
``engine.dedup_and_insert`` on every output — new-candidate mask, count,
and the table contents — since checkpoints and cross-engine gates treat
the table as interchangeable state; both the XLA-side-mask variant and
the fused in-kernel local dedup (VMEM scratch) variant are gated. Runs
in interpret mode on the CPU backend (the TPU lowering is A/B'd in the
hardware session, MEASUREMENTS round-5 plan).
"""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "examples"))

import jax.numpy as jnp

from stateright_tpu.tpu.engine import (dedup_and_insert,
                                       first_occurrence_candidates,
                                       host_table_insert)
from stateright_tpu.tpu.hashing import SENTINEL
from stateright_tpu.tpu.pallas_table import (PALLAS_AVAILABLE,
                                             dedup_and_insert_pallas,
                                             pallas_table_capacity_limit)

pytestmark = pytest.mark.skipif(
    not PALLAS_AVAILABLE, reason="pallas not available in this jax build")


def _random_stream(rng, n, resident):
    """Candidates with duplicates, sentinels, and revisits of resident
    fingerprints — every dedup case."""
    fresh = rng.integers(1, 1 << 62, n, dtype=np.uint64)
    out = fresh.copy()
    dup_rows = rng.random(n) < 0.3
    out[dup_rows] = rng.choice(fresh, dup_rows.sum())
    if len(resident):
        rev_rows = rng.random(n) < 0.2
        out[rev_rows] = rng.choice(resident, rev_rows.sum())
    out[rng.random(n) < 0.1] = SENTINEL
    return out


@pytest.mark.parametrize("capacity", [1 << 14, 1 << 15])
@pytest.mark.parametrize("fuse_local", [True, False])
def test_kernel_matches_xla_loop(capacity, fuse_local):
    import jax

    rng = np.random.default_rng(7)
    resident = rng.integers(1, 1 << 62, capacity // 8, dtype=np.uint64)
    table = np.full(capacity, SENTINEL, np.uint64)
    host_table_insert(table, resident)

    # Jit once per capacity: un-jitted calls would recompile the probe
    # while_loop per round (minutes of XLA time for zero extra signal).
    # Stream sizes keep the load factor under 1/2 across all rounds —
    # the engine's growth invariant; an overfull table would spin the
    # probe loop forever (no empty slot ever found).
    j_xla = jax.jit(lambda f, t: dedup_and_insert(f, t, capacity))
    j_pls = jax.jit(lambda f, t: dedup_and_insert_pallas(
        f, t, capacity, fuse_local=fuse_local))
    j_first = jax.jit(first_occurrence_candidates)

    for round_i in range(4):
        fps = _random_stream(rng, 1024, resident)
        d_fps = jnp.asarray(fps)
        m_x, c_x, t_x = j_xla(d_fps, jnp.asarray(table))
        m_p, c_p, cand_p, t_p = j_pls(d_fps, jnp.asarray(table))
        assert np.array_equal(np.asarray(m_x), np.asarray(m_p)), \
            f"mask mismatch round {round_i}"
        assert int(c_x) == int(c_p)
        # The kernel's candidate count must equal the reference local
        # dedup's distinct count (whichever side computed the mask).
        assert int(cand_p) == int(np.asarray(j_first(d_fps)).sum())
        # Tables must agree as SETS (probe claims can land in different
        # slots only if the claim order differs — it must not: same
        # probe sequence, same winner rule).
        assert np.array_equal(np.asarray(t_x), np.asarray(t_p)), \
            f"table mismatch round {round_i}"
        table = np.asarray(t_x)
        resident = table[table != SENTINEL]


def test_engine_parity_2pc():
    """Full engine runs with table_impl='pallas' count identically."""
    from two_phase_commit import TwoPhaseSys

    model = TwoPhaseSys(3)
    xla = model.checker().spawn_tpu_bfs(table_impl="xla").join()
    pls = model.checker().spawn_tpu_bfs(table_impl="pallas").join()
    assert xla.unique_state_count() == pls.unique_state_count() == 288
    assert set(xla.discoveries()) == set(pls.discoveries())


def test_capacity_limit_is_sane():
    """The VMEM-derived gate is a power of two in a plausible range
    (2^20 from the device-kind table when the backend exposes no
    budget — the CPU backend here doesn't)."""
    limit = pallas_table_capacity_limit()
    assert limit >= 1 << 12
    assert limit & (limit - 1) == 0
    assert pallas_table_capacity_limit() == limit  # cached, stable


def test_vmem_budget_of_unknown_device_kind_raises(monkeypatch):
    """No silent default: a device that reports no VMEM budget and is
    missing from the device-kind table is an error."""
    from stateright_tpu.tpu import pallas_table

    class Device:
        device_kind = "TPU v99"

        def memory_stats(self):
            return None

    monkeypatch.setattr(pallas_table.jax, "local_devices",
                        lambda: [Device()])
    with pytest.raises(NotImplementedError, match="TPU v99"):
        pallas_table._vmem_budget_bytes()
    Device.device_kind = "TPU v5 lite"
    assert pallas_table._vmem_budget_bytes() == 16 << 20


def test_capacity_fallback_warns_once():
    """A capacity beyond the VMEM budget degrades to the XLA table with
    a warning (mid-run growth must survive) — emitted once per
    capacity, not once per compiled wave program."""
    import warnings as _w

    from stateright_tpu.tpu import engine
    from stateright_tpu.tpu.engine import dedup_impl

    too_big = pallas_table_capacity_limit() * 2
    engine._PALLAS_DEGRADE_WARNED.discard(too_big)
    with pytest.warns(RuntimeWarning, match="pallas visited table"):
        fn = dedup_impl("pallas", too_big)
    with _w.catch_warnings():
        _w.simplefilter("error")  # the repeat build must stay silent
        fn = dedup_impl("pallas", too_big)
    fps = jnp.asarray(np.array([3, 5, 3, SENTINEL], np.uint64))
    table = jnp.full((too_big,), jnp.uint64(SENTINEL))
    mask, count, cand, _, rounds = fn(fps, table)
    assert int(count) == 2
    assert int(cand) == 2
    assert min(np.asarray(rounds)) >= 1  # the XLA table counts its loops
