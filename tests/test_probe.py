"""The visited-table probe walks only the candidates, compacted into
chunks of ``engine.PROBE_CHUNK`` rows: it must give the same new-row
mask, the same count and the same table contents, as a set, as one
round loop over every row of the wave (kept here as the oracle). Only
the slot a new key lands in may differ.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from stateright_tpu.tpu import engine
from stateright_tpu.tpu.engine import (_STEP_MIX, _TABLE_MIX,
                                       first_occurrence_candidates,
                                       global_insert_counted,
                                       host_table_insert)
from stateright_tpu.tpu.hashing import SENTINEL


def full_width_insert(dedup_fps, candidate, visited, capacity):
    """The probe as one round loop over all rows: every round gathers,
    claims and re-gathers every row, candidate or not."""
    sentinel = jnp.uint64(SENTINEL)
    shift = jnp.uint64(64 - (capacity.bit_length() - 1))
    slot_mask = jnp.int32(capacity - 1)
    idx0 = ((dedup_fps * jnp.uint64(_TABLE_MIX)) >> shift).astype(jnp.int32)
    step = (((dedup_fps * jnp.uint64(_STEP_MIX)) >> shift)
            .astype(jnp.int32) | 1)

    def cond(carry):
        return carry[2].any()

    def body(carry):
        table, idx, pending, is_new = carry
        cur = table[idx]
        found = pending & (cur == dedup_fps)
        empty = pending & (cur == sentinel)
        table = table.at[jnp.where(empty, idx, capacity)].set(
            dedup_fps, mode="drop")
        won = empty & (table[idx] == dedup_fps)
        pending = pending & ~(found | won)
        idx = jnp.where(pending, (idx + step) & slot_mask, idx)
        return table, idx, pending, is_new | won

    visited, _, _, new_mask = jax.lax.while_loop(
        cond, body, (visited, idx0, candidate,
                     jnp.zeros(dedup_fps.shape, bool)))
    return new_mask, jnp.sum(new_mask, dtype=jnp.int32), visited


def _home(fps, capacity):
    shift = np.uint64(64 - (capacity.bit_length() - 1))
    with np.errstate(over="ignore"):
        return (fps * np.uint64(_TABLE_MIX)) >> shift


def _keys(rng, n):
    return rng.integers(1, 1 << 63, n, dtype=np.uint64)


def _cases():
    """``(name, chunk, capacity, fps, candidate or None, resident)``;
    a None candidate is the wave's own: first occurrences."""
    rng = np.random.default_rng(26)
    chunk = 64
    yield "all_sentinel", chunk, 1 << 12, np.full(
        300, SENTINEL, np.uint64), None, _keys(rng, 100)
    fps = _keys(rng, 300)
    yield "no_candidate", chunk, 1 << 12, fps, np.zeros(300, bool), fps[:50]
    fps = _keys(rng, 320)
    yield "all_candidates", chunk, 1 << 12, fps, None, _keys(rng, 200)
    # c = 3*chunk + 1 candidates among sentinel rows and duplicates
    fps = np.full(1000, SENTINEL, np.uint64)
    pos = rng.choice(1000, 3 * chunk + 1, replace=False)
    fps[pos] = _keys(rng, 3 * chunk + 1)
    yield "one_past_chunks", chunk, 1 << 12, fps, None, fps[pos[:40]]
    # the real chunk: 2 * PROBE_CHUNK + 1 candidates in a wider wave
    big = 2 * engine.PROBE_CHUNK + 1
    fps = np.full(3 * engine.PROBE_CHUNK, SENTINEL, np.uint64)
    pos = rng.choice(len(fps), big, replace=False)
    fps[pos] = _keys(rng, big)
    yield ("one_past_real_chunks", engine.PROBE_CHUNK, 1 << 17, fps, None,
           fps[pos[:3000]])
    fresh = _keys(rng, 60)
    resident = _keys(rng, 100)
    dups = np.concatenate([np.repeat(fresh, 9), np.repeat(resident[:30], 7),
                           np.full(50, SENTINEL, np.uint64)])
    rng.shuffle(dups)
    yield "heavy_duplicates", chunk, 1 << 12, dups, None, resident
    # load 1/2 before the wave: long probe chains
    cap = 1 << 11
    resident = _keys(rng, cap // 2)
    fps = np.concatenate([rng.choice(resident, 150), _keys(rng, 150)])
    rng.shuffle(fps)
    yield "half_full_table", chunk, cap, fps, None, resident
    # distinct keys that share one empty home slot
    cap = 1 << 10
    pool = _keys(rng, 200_000)
    homes = _home(pool, cap)
    slot = np.bincount(homes.astype(np.int64)).argmax()
    same = pool[homes == slot][:40]
    fps = np.concatenate([same, _keys(rng, 200)])
    rng.shuffle(fps)
    yield "contended_slot", 8, cap, fps, None, np.zeros(0, np.uint64)


CASES = list(_cases())


@pytest.mark.parametrize("name,chunk,capacity,fps,candidate,resident",
                         CASES, ids=[c[0] for c in CASES])
def test_chunked_probe_matches_full_width_loop(monkeypatch, name, chunk,
                                               capacity, fps, candidate,
                                               resident):
    monkeypatch.setattr(engine, "PROBE_CHUNK", chunk)
    table = np.full(capacity, SENTINEL, np.uint64)
    host_table_insert(table, resident)
    d_fps = jnp.asarray(fps)
    cand = (first_occurrence_candidates(d_fps) if candidate is None
            else jnp.asarray(candidate))

    m_ref, n_ref, t_ref = jax.jit(
        lambda f, c, t: full_width_insert(f, c, t, capacity))(
            d_fps, cand, jnp.asarray(table))
    m, n, t, rounds = jax.jit(
        lambda f, c, t: global_insert_counted(f, c, t, capacity))(
            d_fps, cand, jnp.asarray(table))

    assert np.array_equal(np.asarray(m), np.asarray(m_ref)), name
    assert int(n) == int(n_ref) == int(np.asarray(m_ref).sum()), name
    t, t_ref = np.asarray(t), np.asarray(t_ref)
    assert (np.sort(t[t != SENTINEL]) == np.sort(t_ref[t_ref != SENTINEL])
            ).all(), name
    # each chunk takes a trip at least; no trip without a candidate
    c = int(np.asarray(cand).sum())
    assert int(rounds) >= -(-c // engine.probe_chunk(len(fps))), name
    assert (int(rounds) == 0) == (c == 0), name
