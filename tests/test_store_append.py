"""The fused waves' store: the admitted rows appended a chunk at a time
(``engine.append_rows``) against the full-window append it replaced,
which this file keeps as the oracle.

With ``APPEND_CHUNK`` made small, the chunked store must write the same
rows ``[tail, tail + new_count)``, raise the same error flag and count
the same eventually bits as the full-window one, and leave the rows
below ``tail`` alone; and whole checks through the fused engine and the
sharded-fused engine, over waves of several trips, must admit the host
checker's states and lay the arena out as a one-trip store does.
"""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "examples"))

from stateright_tpu.model import Expectation  # noqa: E402
from stateright_tpu.tpu import engine  # noqa: E402
from stateright_tpu.tpu.engine import (append_window,  # noqa: E402
                                       compaction_order)
from stateright_tpu.tpu.fused import append_successors  # noqa: E402
from stateright_tpu.tpu.packing import PackedLayout  # noqa: E402
from stateright_tpu.tpu.sharded_fused import append_received  # noqa: E402

#: the chunk the tests walk: small, so a wave takes several trips
CHUNK = 8
#: arena rows
UCAP = 64
#: lanes of a row, the lane that flags an error, and a packing of them
W, ERR_LANE = 5, 3
LAYOUT = PackedLayout([6, 5, 4, 1, 8], W)


@pytest.fixture
def small_chunk(monkeypatch):
    monkeypatch.setattr(engine, "APPEND_CHUNK", CHUNK)


def _full_window_successors(arena, tail, new_mask, succ_flat, path_fps,
                            bfps, cleared, layout, err_lane):
    """The fused wave's store as it was: every one of the S rows
    gathered, packed and written at ``tail``."""
    S, F = new_mask.shape[0], new_mask.shape[0] // bfps.shape[0]
    comp = compaction_order(new_mask)
    new_count = jnp.sum(new_mask, dtype=jnp.int32)
    live = jnp.arange(S) < new_count
    new_vecs = succ_flat[comp]
    new_ebits = cleared[comp // F]
    err = jnp.any((new_vecs[:, err_lane] != 0) & live)
    if layout is not None:
        new_vecs = layout.pack(new_vecs)
    rows = (new_vecs, path_fps[comp], bfps[comp // F], new_ebits)
    arena = tuple(jax.lax.dynamic_update_slice(
        a, r, (tail,) + (jnp.int64(0),) * (a.ndim - 1))
        for a, r in zip(arena, rows))
    pending = jnp.sum((new_ebits != 0) & live, dtype=jnp.int32)
    return arena, err, pending


def _full_window_received(arena, tail, new_mask, recv_vecs, recv_path,
                          recv_parent, recv_ebits, layout, err_lane):
    """The mesh owner's store as it was: every received row gathered and
    written at ``tail``."""
    RR = new_mask.shape[0]
    comp = compaction_order(new_mask)
    new_vecs = recv_vecs[comp]
    err_col = (new_vecs[:, err_lane] if layout is None
               else layout.lane(new_vecs, err_lane))
    err = jnp.any((err_col != 0) & (jnp.arange(RR) < jnp.sum(new_mask)))
    rows = (new_vecs, recv_path[comp], recv_parent[comp], recv_ebits[comp])
    arena = tuple(jax.lax.dynamic_update_slice(
        a, r, (tail,) + (jnp.int64(0),) * (a.ndim - 1))
        for a, r in zip(arena, rows))
    return arena, err


def _mask(kind, S, rng):
    if kind == "none":
        return np.zeros(S, bool)
    if kind == "all":
        return np.ones(S, bool)
    if kind == "chunks":  # an exact multiple of the chunk
        mask = np.zeros(S, bool)
        mask[rng.choice(S, 2 * CHUNK, replace=False)] = True
        return mask
    return rng.random(S) < 0.4


def _arena(rng, words):
    return (jnp.asarray(rng.integers(0, 2**31, (UCAP, words)), jnp.uint32),
            jnp.asarray(rng.integers(0, 2**62, UCAP), jnp.uint64),
            jnp.asarray(rng.integers(0, 2**62, UCAP), jnp.uint64),
            jnp.asarray(rng.integers(0, 4, UCAP), jnp.uint32))


def _check_rows(got, want, before, tail, count):
    """Rows [tail, tail + count) as the oracle wrote them, and every row
    below tail as it was."""
    for g, w, b in zip(got, want, before):
        g, w, b = np.asarray(g), np.asarray(w), np.asarray(b)
        np.testing.assert_array_equal(g[tail:tail + count],
                                      w[tail:tail + count])
        np.testing.assert_array_equal(g[:tail], b[:tail])


#: (frontier rows B, fanout F): S = 24 rows, a multiple of the chunk,
#: and S = 25, not one
SHAPES = [(4, 6), (5, 5)]
MASKS = ["none", "all", "chunks", "some"]


@pytest.mark.parametrize("packed", [False, True])
@pytest.mark.parametrize("at_edge", [False, True])
@pytest.mark.parametrize("kind", MASKS)
@pytest.mark.parametrize("shape", SHAPES)
def test_fused_store_matches_the_full_window(small_chunk, shape, kind,
                                             at_edge, packed):
    """``at_edge`` puts ``tail`` where the chunks' window ends at the
    arena's last row: a last chunk moved back by the update's clamp
    would overwrite admitted rows there."""
    B, F = shape
    S = B * F
    rng = np.random.default_rng([B, F, MASKS.index(kind), at_edge, packed])
    layout = LAYOUT if packed else None
    vals = rng.integers(0, 16, (S, W))
    vals[:, ERR_LANE] = rng.random(S) < 0.1
    succ_flat = jnp.asarray(vals, jnp.uint32)
    path_fps = jnp.asarray(rng.integers(0, 2**62, S), jnp.uint64)
    bfps = jnp.asarray(rng.integers(0, 2**62, B), jnp.uint64)
    cleared = jnp.asarray(rng.integers(0, 2, B), jnp.uint32)
    new_mask = jnp.asarray(_mask(kind, S, rng))
    tail = UCAP - append_window(S) if at_edge else 3
    assert tail + append_window(S) <= UCAP
    arena = _arena(rng, W if layout is None else layout.packed_width)
    tail_d = jnp.int64(tail)

    want, want_err, want_pending = _full_window_successors(
        arena, tail_d, new_mask, succ_flat, path_fps, bfps, cleared,
        layout, ERR_LANE)
    got, err, pending, trips = jax.jit(
        append_successors, static_argnames=("layout", "err_lane",
                                            "count_pending"))(
        arena, tail_d, new_mask, succ_flat, path_fps, bfps, cleared,
        layout=layout, err_lane=ERR_LANE, count_pending=True)
    count = int(np.sum(new_mask))
    _check_rows(got, want, arena, tail, count)
    assert bool(err) == bool(want_err)
    assert int(pending) == int(want_pending)
    assert int(trips) == -(-count // CHUNK)


@pytest.mark.parametrize("packed", [False, True])
@pytest.mark.parametrize("at_edge", [False, True])
@pytest.mark.parametrize("kind", MASKS)
@pytest.mark.parametrize("RR", [24, 25])
def test_mesh_store_matches_the_full_window(small_chunk, RR, kind, at_edge,
                                            packed):
    rng = np.random.default_rng([RR, MASKS.index(kind), at_edge, packed])
    layout = LAYOUT if packed else None
    vals = rng.integers(0, 16, (RR, W))
    vals[:, ERR_LANE] = rng.random(RR) < 0.1
    recv_vecs = jnp.asarray(vals, jnp.uint32)
    if layout is not None:
        recv_vecs = layout.pack(recv_vecs)
    recv_path = jnp.asarray(rng.integers(0, 2**62, RR), jnp.uint64)
    recv_parent = jnp.asarray(rng.integers(0, 2**62, RR), jnp.uint64)
    recv_ebits = jnp.asarray(rng.integers(0, 2, RR), jnp.uint32)
    new_mask = jnp.asarray(_mask(kind, RR, rng))
    tail = UCAP - append_window(RR) if at_edge else 3
    arena = _arena(rng, recv_vecs.shape[1])
    tail_d = jnp.int64(tail)

    want, want_err = _full_window_received(
        arena, tail_d, new_mask, recv_vecs, recv_path, recv_parent,
        recv_ebits, layout, ERR_LANE)
    got, err, trips = jax.jit(
        append_received, static_argnames=("layout", "err_lane"))(
        arena, tail_d, new_mask, recv_vecs, recv_path, recv_parent,
        recv_ebits, layout=layout, err_lane=ERR_LANE)
    count = int(np.sum(new_mask))
    _check_rows(got, want, arena, tail, count)
    assert bool(err) == bool(want_err)
    assert int(trips) == -(-count // CHUNK)


def test_append_window_is_whole_chunks(small_chunk):
    assert [append_window(n) for n in (1, 7, 8, 9, 16, 17)] == [
        1, 7, 8, 16, 16, 24]


# -- Whole checks -----------------------------------------------------------

def _paxos(clients):
    from paxos import PaxosModelCfg

    return PaxosModelCfg(clients, 3, liveness=True).into_model()


def _twopc():
    from two_phase_commit import TwoPhaseSys

    return TwoPhaseSys(4)


def _arena_rows(c):
    """Each arena array's rows below each shard's tail."""
    n = getattr(c, "_n", 1)
    tails = (np.asarray(c._shard_tails) if n > 1
             else np.array([c._arena_tail]))
    out = []
    for a in c._arena:
        shards = np.split(np.asarray(a), n)
        out.append(np.concatenate([s[:t] for s, t in zip(shards, tails)]))
    return out


#: the dispatch counts the store leaves alone
SAME = ("waves", "successors", "candidates", "novel", "probe_rounds",
        "dedup_rounds", "eventually_pending")


def _check_against_one_trip(monkeypatch, model, target=None, **kw):
    """The check with chunks of ``CHUNK`` rows against the same check
    with one trip a wave (where every wave's rows fit one chunk, as the
    full window did): the same counts, the same arena below the tails,
    and waves that took more than one trip."""
    def run(chunk):
        monkeypatch.setattr(engine, "APPEND_CHUNK", chunk)
        b = model.checker()
        if target is not None:
            b = b.target_state_count(target)
        return b.spawn_tpu_bfs(fused=True, **kw).join()

    chunked, whole = run(CHUNK), run(1 << 20)
    log = [e for e in chunked.dispatch_log if e["waves"]]
    assert sum(e["append_slots"] for e in log) // CHUNK > sum(
        e["waves"] for e in log)
    # on a mesh the slots are the slowest shard's
    shards = getattr(chunked, "_n", 1)
    assert all(e["novel"] <= shards * e["append_slots"] for e in log)
    assert [[e.get(k) for k in SAME] for e in chunked.dispatch_log] == [
        [e.get(k) for k in SAME] for e in whole.dispatch_log]
    for got, want in zip(_arena_rows(chunked), _arena_rows(whole)):
        np.testing.assert_array_equal(got, want)
    return chunked


def _check_against_host(model, c):
    host = model.checker().spawn_bfs().join()
    assert c.unique_state_count() == host.unique_state_count()
    assert c.state_count() == host.state_count()
    assert set(c.discoveries()) == set(host.discoveries())
    for name, path in c.discoveries().items():
        prop = model.property(name)
        holds = prop.condition(model, path.last_state())
        assert holds == (prop.expectation is Expectation.SOMETIMES)


def _pending_rows(c):
    """Arena rows admitted by the waves (the seeded initial rows
    excluded) whose eventually bits are still set."""
    ebits = _arena_rows(c)[3]
    return int(np.sum(ebits[len(c.model().init_states()):] != 0))


def test_fused_paxos3_in_small_chunks(monkeypatch):
    """Paxos with 3 clients and "eventually chosen", stopped after a few
    thousand states, at a small batch: its waves admit hundreds of rows
    each, so tens of trips."""
    c = _check_against_one_trip(monkeypatch, _paxos(3), target=3000,
                                batch_size=16)
    assert sum(e["eventually_pending"] for e in c.dispatch_log) == (
        _pending_rows(c))


@pytest.mark.parametrize("make", [lambda: _paxos(1), _twopc],
                         ids=["paxos1", "twopc4"])
def test_fused_checks_in_small_chunks_match_host(monkeypatch, make):
    model = make()
    c = _check_against_one_trip(monkeypatch, model, batch_size=16)
    _check_against_host(model, c)


def test_sharded_fused_twopc_in_small_chunks_matches_host(monkeypatch):
    from jax.sharding import Mesh

    model = _twopc()
    c = _check_against_one_trip(
        monkeypatch, model, batch_size=16,
        mesh=Mesh(np.array(jax.devices()[:4]), ("shard",)))
    _check_against_host(model, c)
