"""The wave program (``engine.build_wave``) against host truth.

A few BFS levels are driven through the jitted wave program, and every
output is recomputed on the host from the model itself: the successor
count (``Model.next_states``), the property conditions, the terminal
rows, the novel rows (the batch's host successors, in frontier-row then
action order, whose dedup fingerprint was not yet in the table nor
earlier in the wave), their path fingerprints and parent rows, the
candidate count, the overflow flag, and the merged table as a set.
Paxos's device step emits a state's successors in its network's
envelope order, not the host's action order, so there the novel rows
are compared per parent row as sets.
Under symmetry the dedup fingerprint is that of the device model's own
``representative`` (an exact canonicalization, finer than the host
model's value-only sort), while paths keep the original's.
"""

import os
import sys

import numpy as np
import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_ROOT, "examples"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from increment_lock import IncrementLockModel  # noqa: E402
from paxos import PaxosModelCfg  # noqa: E402
from two_phase_commit import TwoPhaseSys  # noqa: E402

from stateright_tpu.tpu.engine import (build_wave,  # noqa: E402
                                       host_table_insert)
from stateright_tpu.tpu.hashing import SENTINEL, host_fp64  # noqa: E402
from stateright_tpu.tpu.packing import compile_layout  # noqa: E402

CAP = 1 << 14

#: case -> (model factory, symmetry, batch rows, output rung, waves,
#: whether the device emits successors in the host's action order)
CASES = {
    "2pc4": (lambda: TwoPhaseSys(4), False, 64, None, 4, True),
    "2pc4-sym": (lambda: TwoPhaseSys(4), True, 64, None, 4, True),
    "paxos2": (lambda: PaxosModelCfg(2, 3).into_model(), False, 32,
               None, 4, False),
    "increment_lock2": (lambda: IncrementLockModel(2), False, 16, None,
                        6, True),
    # A rung of 8 rows under a wave whose novel set is larger.
    "2pc4-overflow": (lambda: TwoPhaseSys(4), False, 64, 8, 3, True),
}


def _fp(dm, state) -> int:
    return host_fp64(np.asarray(dm.encode(state), np.uint32))


@pytest.mark.parametrize("case", sorted(CASES))
def test_wave_matches_host_truth(case):
    make, use_sym, B, out_rows, waves, ordered = CASES[case]
    model = make()
    dm = model.device_model()
    W, F = dm.state_width, dm.max_fanout
    layout = compile_layout(dm.lane_bits(), W)
    device_props = dm.device_properties()
    props = [p for p in model.properties() if p.name in device_props]
    wave = build_wave(dm, B, CAP,
                      prop_fns=[device_props[p.name] for p in props],
                      use_sym=use_sym, out_rows=out_rows, layout=layout)
    K = B * F if out_rows is None else out_rows

    rep = jax.jit(dm.representative) if use_sym else None

    def dedup_fp(state):
        vec = np.asarray(dm.encode(state), np.uint32)
        if rep is not None:
            vec = np.asarray(rep(vec), np.uint32)
        return host_fp64(vec)

    frontier = list(model.init_states())
    visited = {dedup_fp(s) for s in frontier}
    table = np.full((CAP,), SENTINEL, np.uint64)
    host_table_insert(table, np.fromiter(visited, np.uint64, len(visited)))
    table = jnp.asarray(table)
    overflowed = False
    for wave_i in range(waves):
        batch = frontier[:B]
        frontier = frontier[B:]
        n = len(batch)
        assert n, f"{case}: frontier ran dry at wave {wave_i}"
        vecs = np.zeros((B, W), np.uint32)
        vecs[:n] = np.stack([np.asarray(dm.encode(s), np.uint32)
                             for s in batch])
        valid = np.arange(B) < n
        (conds, succ_count, cand_count, terminal, new_count, new_vecs,
         new_fps, new_parent, new_mask, overflow, table) = wave(
            jnp.asarray(layout.pack_np(vecs)), jnp.asarray(valid), table)

        succs = [model.next_states(s) for s in batch]
        where = (case, wave_i)
        assert int(succ_count) == sum(map(len, succs)), where
        for p, cond in zip(props, conds):
            want = [bool(p.condition(model, s)) for s in batch]
            assert np.asarray(cond)[:n].tolist() == want, (where, p.name)
        assert np.asarray(terminal).tolist() == (
            [not ss for ss in succs] + [False] * (B - n)), where

        novel, seen = [], set()
        for row, ss in enumerate(succs):
            for s in ss:
                key = dedup_fp(s)
                if key not in seen:
                    seen.add(key)
                    if key not in visited:
                        novel.append((row, s))
        assert int(cand_count) == len(seen), where
        visited |= {dedup_fp(s) for _, s in novel}
        assert int(new_count) == len(novel), where
        assert int(np.asarray(new_mask).sum()) == len(novel), where
        assert bool(overflow) == (len(novel) > K), where
        overflowed |= bool(overflow)
        k = min(len(novel), K)
        got = list(zip(
            np.asarray(new_parent)[:k].tolist(),
            map(tuple, layout.unpack_np(np.asarray(new_vecs)[:k]).tolist()),
            np.asarray(new_fps)[:k].tolist()))
        want = [(row, tuple(np.asarray(dm.encode(s), np.uint32).tolist()),
                 _fp(dm, s)) for row, s in novel[:k]]
        if not ordered:
            assert [g[0] for g in got] == sorted(g[0] for g in got), where
            got, want = sorted(got), sorted(want)
        assert got == want, where
        t = np.asarray(table)
        assert set(t[t != SENTINEL].tolist()) == visited, where
        frontier.extend(s for _, s in novel)
    assert overflowed == (out_rows is not None), case
