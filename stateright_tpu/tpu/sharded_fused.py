"""Fused multi-chip BFS: per-shard device arenas + in-loop all-to-all.

``ShardedTpuBfsChecker`` routes each wave through the host (per-shard
batch assembly up, per-shard survivor blocks down), so multi-chip wall
time inherits the same host-boundary tax the fused single-chip engine
removed. This engine keeps the whole checker state device-resident *per
shard* and runs up to ``waves_per_dispatch`` waves per dispatch:

- **Per-shard arena**: shard ``i`` owns fingerprints with
  ``fp % n == i`` and appends every state it owns to its local arena
  (vecs/fps/parent-fps/ebits) — rows ``[head_i, tail_i)`` are its
  frontier share. Ownership doubles as load balancing, exactly like the
  unfused engine.
- **In-loop shuffle**: each wave, every shard expands its share,
  fingerprints successors, buckets them by owner, and rounds of
  ``lax.all_to_all`` (ICI on a TPU slice) route them home, where the
  owner dedups against its local table slice and appends survivors —
  all inside one ``lax.while_loop`` under ``shard_map``.
- **Lockstep stop conditions**: every shard computes identical global
  predicates (``psum`` of live rows / successor counts, ``pmax`` of
  arena/table occupancy, replicated discovery slots), so the loop stays
  collectively synchronized and exits together — growth and checkpoints
  then happen between dispatches, at rest.
- **Shard-major discovery order**: per wave, each shard proposes its
  first-hit fingerprint per property; an ``all_gather`` picks the lowest
  shard index with a hit — the same identity the unfused sharded engine
  derives on the host from its concatenated batch, preserved here so the
  two engines are discovery-identical (and, like the reference's
  multithreaded BFS, not guaranteed shortest: `checker.rs:115-118`).
- **Sized buckets**: an exchange round sends each owner a bucket of
  ``CAP = exchange_bucket_rows(B*F, n)`` rows, a sender's balanced
  share, so an owner receives ``n*CAP`` rows a round, not the ``n*B*F``
  a wave could send it. A wave takes as many rounds as the fullest
  bucket on any shard needs (a ``pmax``: at least one, at most ``n``),
  and each round's owner dedups, probes and appends what it received.

Host-per-dispatch traffic is one packed per-shard stats array; parent
rows are fetched lazily, as in the single-chip fused engine.

The wave names its stages as the single-chip wave does (``load``,
``properties``, ``expand``, ``fingerprint``, ``local_dedup``,
``probe``, ``store``), plus ``exchange``: the sender-side duplicate
collapse, the owner bucketing and the all-to-alls. Each dispatch counts
the mesh's slowest shard's loop rounds per wave, the exchange rounds
(``exchange_rounds``) and the successor rows sent to another shard
(``exchange_rows``); the host loop opens the same ``fused.*`` spans.
"""

from __future__ import annotations

import time
from functools import partial
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ._compat import shard_map

from ..model import Expectation
from ..resilience.membership import EpochOwnership, OwnerMap
from .engine import (compaction_order, dedup_and_insert,
                     dedup_and_insert_counted, eval_properties,
                     expand_frontier, fingerprint_successors,
                     first_occurrence_unscoped, host_table_insert,
                     pick_bucket, probe_chunk)
from .fused import (FusedTpuBfsChecker, ST_CAND, ST_DEDUP_ROUNDS, ST_DISC,
                    ST_ERR, ST_HEAD, ST_OCC, ST_PROBE_ROUNDS, ST_SUCC,
                    ST_TAIL, ST_TARGET, ST_WAVES, _pow2, _releasing)
from .hashing import SENTINEL

__all__ = ["ShardedFusedTpuBfsChecker"]

# The stats row is the single-chip layout with two more slots before
# the discovery fingerprints: the successor rows the dispatch's waves
# sent to another shard, summed over the mesh, and the exchange rounds
# they took.
ST_EXCHANGE_ROWS = ST_DISC
ST_EXCHANGE_ROUNDS = ST_DISC + 1
SH_DISC = ST_DISC + 2


def exchange_bucket_rows(rows: int, shards: int) -> int:
    """Rows of each owner's bucket in one exchange round, for senders of
    ``rows`` successor rows a wave: the balanced share
    ``ceil(rows / shards)``, rounded up to a multiple of 8. A sender
    whose rows spread evenly over the owners sends them in one round."""
    share = -(-rows // shards)
    return -(-share // 8) * 8


def exchange_window_rows(rows: int, shards: int) -> int:
    """Arena rows past a shard's tail that one wave's appends may
    write. Round ``r`` appends a window of ``shards * CAP`` rows after
    what the rounds before it admitted (at most ``shards * r * CAP``),
    and a wave takes at most ``ceil(rows / CAP)`` rounds: so
    ``shards * ceil(rows / CAP) * CAP``, which is ``shards * rows``
    where ``CAP`` divides ``rows``."""
    cap = exchange_bucket_rows(rows, shards)
    return shards * -(-rows // cap) * cap


class ShardedFusedTpuBfsChecker(EpochOwnership, FusedTpuBfsChecker):
    """The fused engine over a device mesh. ``batch_size`` is per shard.

    ``exchange_novel_only`` (default on): run the intra-wave local dedup
    on the sender side, before the in-loop all-to-all, so duplicate
    successors die in their producer's local pass instead of riding the
    interconnect (same rule and bit-identity argument as the classic
    sharded engine)."""

    _ENGINE_ID = "sharded_fused"

    def __init__(self, builder, batch_size: int = 512,
                 mesh: Optional[Mesh] = None,
                 exchange_novel_only: Optional[bool] = None, **kwargs):
        if mesh is None:
            mesh = Mesh(np.array(jax.devices()), ("shard",))
        self._mesh = mesh
        self._n = mesh.devices.size
        # Epoch-versioned ownership (resilience.membership): identity
        # assignment unless remapped at a rest point; the dispatch
        # cache is epoch-keyed, exactly like the unfused engine.
        self._owner_map = OwnerMap.identity(self._n)
        self._exchange_novel = (True if exchange_novel_only is None
                                else bool(exchange_novel_only))
        super().__init__(builder, batch_size=batch_size, **kwargs)

    # -- Sharded device state ---------------------------------------------

    def _shard_spec(self):
        return NamedSharding(self._mesh, P("shard"))

    def _new_table(self, fps) -> jax.Array:
        """[n * capacity] visited table — shard ``i``'s slice is an
        open-addressing table over its owned fingerprints
        (``fp % n == i``). Sharded arrays stay flat on the shard axis so
        every ``shard_map`` local view is exactly one shard's block."""
        n, cap = self._n, self._capacity
        table = np.full((n, cap), SENTINEL, np.uint64)
        buckets: list = [[] for _ in range(n)]
        for fp in fps:
            buckets[self._owner(int(fp))].append(fp)
        for i, bucket in enumerate(buckets):
            host_table_insert(table[i], np.fromiter(
                (int(f) for f in bucket), np.uint64, len(bucket)))
        self._seed_occ = [len(b) for b in buckets]
        self._shard_occ = self._seed_occ
        self._resident = len(fps)
        return jax.device_put(table.reshape(n * cap), self._shard_spec())

    def shard_occupancy(self) -> list:
        """Visited-table entries held by each shard, as of the last
        processed dispatch: where the states landed on the mesh."""
        with self._lock:
            return [int(o) for o in self._shard_occ]

    def _table_bytes(self, capacity: int) -> int:
        # Capacity is PER SHARD; the device footprint is the mesh's.
        return self._n * capacity * 8

    def _roll_fn(self, ucap: int, dtype, width: int = 0):
        """Per-shard arena-span shift under ``shard_map``: each shard's
        local slice rolls down by ITS OWN head (the shifts ride in a
        sharded [n] array), so every shard's live window lands at its
        slice base."""
        key = ("roll", ucap, str(dtype), width)
        cached = self._wave_cache.get(key)
        if cached is not None:
            return cached

        def roll_local(arr, shift):
            return jnp.roll(arr, -shift[0], axis=0)

        sharded = shard_map(
            roll_local, mesh=self._mesh,
            in_specs=(P("shard"), P("shard")), out_specs=P("shard"),
            check_vma=False)
        jitted = jax.jit(sharded, donate_argnums=(0,))
        spec = self._shard_spec()
        n = self._n
        shape = ((n * ucap, width) if width else (n * ucap,))
        jitted = self._aot(jitted, (
            jax.ShapeDtypeStruct(shape, dtype, sharding=spec),
            jax.ShapeDtypeStruct((n,), jnp.int64, sharding=spec)))
        self._wave_cache[key] = jitted
        return jitted

    # -- Dispatch program --------------------------------------------------

    def _dispatch_fn(self, batch: int, capacity: int, ucap: int):
        key = ("sharded-dispatch", batch, capacity, ucap,
               self._owner_map.epoch)
        cached = self._wave_cache.get(key)
        if cached is not None:
            return cached
        dm = self._dm
        mesh = self._mesh
        n = self._n
        B, F, W, K = batch, self._F, self._W, self._K
        Wr = self._Wrow
        layout = self._wave_layout()
        S = B * F        # successors produced per shard per wave
        CAP = exchange_bucket_rows(S, n)  # rows per owner a round
        RR = n * CAP     # rows a shard receives per round
        R = n * S        # rows a shard can admit per wave
        WIN = exchange_window_rows(S, n)  # arena rows a wave may write
        prop_fns = list(self._prop_fns)
        use_sym = self._use_symmetry
        exchange_novel = self._exchange_novel
        properties = self._properties
        Pn = len(properties)
        sentinel = jnp.uint64(SENTINEL)
        err_lane = dm.error_lane
        # Ownership assignment baked into the compiled dispatch (the
        # cache key carries the epoch); identity keeps the raw modulo.
        assign = (None if self._owner_map.is_identity
                  else jnp.asarray(
                      np.asarray(self._owner_map.assignment(),
                                 np.int32)))

        def propose_first(hit, bfps):
            """This shard's (has-hit, first-hit fp) for one property."""
            row = jnp.argmax(hit)
            return hit.any(), bfps[row]

        def combine_first(disc_i, has, fp):
            """Lowest shard index with a hit wins — the shard-major
            order of the unfused engine's concatenated batch."""
            all_has = jax.lax.all_gather(has, "shard")   # [n]
            all_fp = jax.lax.all_gather(fp, "shard")     # [n]
            winner = jnp.argmax(all_has)                 # first True
            found = all_has.any()
            return jnp.where((disc_i == sentinel) & found,
                             all_fp[winner], disc_i)

        def wave(carry):
            """Exchange round ``r`` of the current wave. Each round
            expands the same frontier rows (those below the tail its
            wave began with), so every round derives the same
            owner-sorted successors and sends the next bucket of them;
            the last round moves the head on. A wave of one round is the
            whole wave."""
            (vecs_a, fps_a, par_a, eb_a, visited, head, tail, occ,
             succ_total, cand_total, sent_total, err, disc, waves, rounds,
             x_rounds, r, wave_tail, target) = carry
            first = r == 0
            wave_tail = jnp.where(first, tail, wave_tail)
            with jax.named_scope("load"):
                # Local frontier slice (scalars head/tail are per shard).
                idx = head + jnp.arange(B, dtype=jnp.int64)
                valid = idx < wave_tail
                idx_c = jnp.minimum(idx, ucap - 1)
                # Per-shard arenas store PACKED rows; unpack for compute.
                bvecs = vecs_a[idx_c]
                if layout is not None:
                    bvecs = layout.unpack(bvecs)
                bfps = fps_a[idx_c]
                bebits = eb_a[idx_c]

            conds = eval_properties(prop_fns, bvecs)
            for i, prop in enumerate(properties):
                if prop.expectation is Expectation.ALWAYS:
                    hit = valid & ~conds[i]
                elif prop.expectation is Expectation.SOMETIMES:
                    hit = valid & conds[i]
                else:
                    continue
                disc = disc.at[i].set(
                    combine_first(disc[i], *propose_first(hit, bfps)))

            succ_flat, sflat, succ_count, terminal = expand_frontier(
                dm, bvecs, valid)
            dedup_fps, path_fps = fingerprint_successors(
                dm, succ_flat, sflat, use_sym)

            cleared = bebits
            for i, prop in enumerate(properties):
                if prop.expectation is Expectation.EVENTUALLY:
                    cleared = cleared & ~jnp.where(
                        conds[i], jnp.uint32(1 << i), jnp.uint32(0))
            for i, prop in enumerate(properties):
                if prop.expectation is Expectation.EVENTUALLY:
                    hit = valid & terminal & ((cleared >> i) & 1
                                              ).astype(bool)
                    disc = disc.at[i].set(
                        combine_first(disc[i], *propose_first(hit, bfps)))

            # Pack before the in-loop exchange: the ICI moves Wr words
            # per state, and the owner appends the received rows to its
            # arena without ever unpacking them.
            with jax.named_scope("store"):
                succ_store = (succ_flat if layout is None
                              else layout.pack(succ_flat))

            # Bucket successors by owner and route bucket r of each home
            # (ICI all-to-alls, as in the unfused engine). With
            # exchange_novel_only, sender-side local dedup thins the
            # candidate stream first (same-shard later duplicates could
            # never win the owner's first-occurrence rule anyway).
            with jax.named_scope("exchange"):
                parent_fps = jnp.repeat(bfps, F)
                child_ebits = jnp.repeat(cleared, F)
                if exchange_novel:
                    send_mask = first_occurrence_unscoped(dedup_fps)[0]
                else:
                    send_mask = sflat
                part = (dedup_fps % n).astype(jnp.int32)
                dest = part if assign is None else assign[part]
                # Successor rows that leave this shard.
                sent = jnp.sum(send_mask & (dest != jax.lax.axis_index(
                    "shard")), dtype=jnp.int64)
                owner = jnp.where(send_mask, dest, n)
                order = jnp.argsort(owner, stable=True).astype(jnp.int32)
                starts = jnp.searchsorted(
                    owner[order], jnp.arange(n + 1)).astype(jnp.int32)
                counts = starts[1:] - starts[:-1]   # rows for each owner
                # Every shard takes the rounds that the fullest bucket on
                # any shard needs.
                n_rounds = jnp.maximum(jax.lax.pmax(
                    -(-jnp.max(counts) // CAP), "shard"), 1)
                # Bucket d of this round: rows starts[d] + r*CAP + j of
                # the owner-sorted order, those below its count.
                j = r * CAP + jnp.arange(CAP, dtype=jnp.int32)
                take = (j < counts[:, None]).reshape(RR)
                src = order[jnp.minimum(starts[:n, None] + j,
                                        S - 1)].reshape(RR)

                def bucket(x, fill):
                    keep = take.reshape((RR,) + (1,) * (x.ndim - 1))
                    return jnp.where(keep, x[src], fill)

                a2a = partial(jax.lax.all_to_all, axis_name="shard",
                              split_axis=0, concat_axis=0, tiled=True)
                recv_vecs = a2a(bucket(succ_store, 0).reshape(
                    n, CAP, Wr)).reshape(RR, Wr)
                recv_dedup = a2a(bucket(dedup_fps, sentinel).reshape(
                    n, CAP)).reshape(RR)
                recv_path = a2a(bucket(path_fps, sentinel).reshape(
                    n, CAP)).reshape(RR)
                recv_parent = a2a(bucket(parent_fps, sentinel).reshape(
                    n, CAP)).reshape(RR)
                recv_ebits = a2a(bucket(child_ebits, 0).reshape(
                    n, CAP)).reshape(RR)

            new_mask, new_count, cand_count, visited, wave_rounds = (
                dedup_and_insert_counted(recv_dedup, visited, capacity))

            # Full-window append on purpose: a cond-narrowed window
            # breaks the donated arena's in-place aliasing (see the
            # single-chip fused wave).
            with jax.named_scope("store"):
                comp = compaction_order(new_mask)
                new_vecs = recv_vecs[comp]
                if err_lane is not None:
                    # Rows are packed here; extract just the error lane
                    # from the packed words (no full unpack).
                    err_col = (new_vecs[:, err_lane] if layout is None
                               else layout.lane(new_vecs, err_lane))
                    err = err | jnp.any((err_col != 0)
                                        & (jnp.arange(RR) < new_count))
                vecs_a = jax.lax.dynamic_update_slice(
                    vecs_a, new_vecs, (tail, jnp.int64(0)))
                fps_a = jax.lax.dynamic_update_slice(
                    fps_a, recv_path[comp], (tail,))
                par_a = jax.lax.dynamic_update_slice(
                    par_a, recv_parent[comp], (tail,))
                eb_a = jax.lax.dynamic_update_slice(
                    eb_a, recv_ebits[comp], (tail,))

            nc = new_count.astype(jnp.int64)
            # A wave's successors and sent rows count in its first round.
            succ_all, cand_all, sent_all = jax.lax.psum(jnp.stack(
                [jnp.where(first, succ_count, 0),
                 cand_count.astype(jnp.int64),
                 jnp.where(first, sent, 0)]), "shard")
            # A round waits for its slowest shard at the next exchange:
            # the mesh's rounds are each loop's most on any shard.
            wave_rounds = jax.lax.pmax(jnp.stack(wave_rounds), "shard")
            last = r + 1 >= n_rounds
            return (vecs_a, fps_a, par_a, eb_a, visited,
                    jnp.where(last, jnp.minimum(head + B, wave_tail), head),
                    tail + nc, occ + nc,
                    succ_total + succ_all, cand_total + cand_all,
                    sent_total + sent_all, err, disc,
                    waves + last.astype(jnp.int64),
                    tuple(k + wave_rounds[i] for i, k in enumerate(rounds)),
                    x_rounds + 1, jnp.where(last, 0, r + 1), wave_tail,
                    target)

        def cond(carry):
            (_, _, _, _, _, head, tail, occ, succ_total, _cand, _sent, err,
             disc, waves, _rounds, _x_rounds, r, _wave_tail, target) = carry
            # Every operand is either replicated (succ_total, disc,
            # waves, target) or globally reduced, so all shards agree.
            # The TPU lowers 64-bit all-reduces for sums only, so the
            # maxima run in int32: a shard's rows stay far below 2^31
            # (a 2^31-entry table alone would fill a 16 GB chip).
            live = jax.lax.psum(tail - head, "shard")
            worst_tail = jax.lax.pmax(tail.astype(jnp.int32), "shard")
            worst_occ = jax.lax.pmax(occ.astype(jnp.int32), "shard")
            any_err = jax.lax.pmax(err.astype(jnp.int32), "shard") > 0
            more = (waves < K) & (live > 0) & ~any_err
            more = more & (worst_tail + WIN <= ucap)
            more = more & (worst_occ + R <= capacity // 2)
            if Pn:
                more = more & ~jnp.all(disc != sentinel)
            # A wave's later rounds always run: the loop stops between
            # waves (r is the same on every shard).
            return (r > 0) | (more & (succ_total < target))

        def local(vecs_a, fps_a, par_a, eb_a, visited, disc, stats_in):
            # Per-shard views: vecs_a [U, W], visited [capacity],
            # stats_in [1, L] (this shard's head/tail/occ/err +
            # replicated succ_total/target), disc [P] replicated. The
            # ST_* row layout is identical on input and output so a
            # successor dispatch chains on this one's device-resident
            # stats without a host round trip.
            head, tail, occ = (stats_in[0, i]
                               for i in (ST_HEAD, ST_TAIL, ST_OCC))
            succ_total = stats_in[0, ST_SUCC]
            cand_total = stats_in[0, ST_CAND]
            target = stats_in[0, ST_TARGET]
            # Waves, loop rounds, rows sent and exchange rounds count
            # per dispatch; a dispatch starts and ends between waves.
            carry = (vecs_a, fps_a, par_a, eb_a, visited, head, tail,
                     occ, succ_total, cand_total, jnp.zeros((), jnp.int64),
                     stats_in[0, ST_ERR] != 0, disc,
                     jnp.zeros((), jnp.int64), (jnp.int32(0),) * 2,
                     jnp.int32(0), jnp.int32(0), tail, target)
            (vecs_a, fps_a, par_a, eb_a, visited, head, tail, occ,
             succ_total, cand_total, sent, err, disc, waves, rounds,
             x_rounds, *_) = jax.lax.while_loop(cond, wave, carry)
            local_rounds, probe_rounds = (r.astype(jnp.int64)
                                          for r in rounds)
            # Discovery slots (replicated) ride in each shard's stats row
            # so the host reads one packed array per dispatch.
            stats = jnp.concatenate([
                jnp.stack([head, tail, occ, succ_total, cand_total,
                           target, err.astype(jnp.int64), waves,
                           probe_rounds, local_rounds, sent,
                           x_rounds.astype(jnp.int64)]),
                jax.lax.bitcast_convert_type(disc, jnp.int64)])[None]
            return vecs_a, fps_a, par_a, eb_a, visited, disc, stats

        sharded = shard_map(
            local, mesh=mesh,
            in_specs=(P("shard"), P("shard"), P("shard"), P("shard"),
                      P("shard"), P(), P("shard")),
            out_specs=(P("shard"), P("shard"), P("shard"), P("shard"),
                       P("shard"), P(), P("shard")),
            check_vma=False)
        # stats_in is NOT donated: the host reads dispatch k's stats
        # after dispatch k+1 (which consumes them as input) has launched.
        jitted = jax.jit(sharded, donate_argnums=(0, 1, 2, 3, 4, 5))
        spec = self._shard_spec()
        rep = NamedSharding(mesh, P())

        def sds(shape, dtype, sharding=spec):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

        L = SH_DISC + max(Pn, 1)
        jitted = self._aot(jitted, (
            sds((n * ucap, Wr), jnp.uint32), sds((n * ucap,), jnp.uint64),
            sds((n * ucap,), jnp.uint64), sds((n * ucap,), jnp.uint32),
            sds((n * capacity,), jnp.uint64),
            sds((max(Pn, 1),), jnp.uint64, rep),
            sds((n, L), jnp.int64)))
        if self._prof.enabled:
            # Sharded dispatch programs bypass the shared program cache
            # (the ownership epoch keys them per instance), so static
            # cost capture (obs/prof.py) rides here.
            self._prof.capture(self._prof_key(key), jitted)
        self._wave_cache[key] = jitted
        return jitted

    def _grow_fn(self, old_cap: int, new_cap: int, dtype, width: int = 0):
        """Per-shard arena copy into a bigger buffer (runs under
        shard_map so each shard pads its own rows)."""
        key = ("sharded-grow", old_cap, new_cap, str(dtype), width)
        cached = self._wave_cache.get(key)
        if cached is not None:
            return cached

        def grow_local(arr):
            shape = (new_cap, width) if width else (new_cap,)
            fill = SENTINEL if arr.dtype == jnp.uint64 else 0
            out = jnp.full(shape, fill, arr.dtype)
            start = (0, 0) if width else (0,)
            return jax.lax.dynamic_update_slice(out, arr, start)

        n = self._n
        shape = ((n * old_cap, width) if width else (n * old_cap,))
        jitted = _releasing(self._aot(
            jax.jit(shard_map(
                grow_local, mesh=self._mesh, in_specs=P("shard"),
                out_specs=P("shard"), check_vma=False),
                donate_argnums=(0,)),
            (jax.ShapeDtypeStruct(shape, dtype,
                                  sharding=self._shard_spec()),)))
        self._wave_cache[key] = jitted
        return jitted

    def _rehash_fn(self, old_cap: int, new_cap: int):
        key = ("sharded-rehash", old_cap, new_cap)
        cached = self._wave_cache.get(key)
        if cached is not None:
            return cached

        def rehash_local(old_table):
            # Local view: this shard's [old_cap] slice of the flat table.
            new_table = jnp.full((new_cap,), SENTINEL, jnp.uint64)
            _, _, new_table = dedup_and_insert(old_table, new_table,
                                               new_cap)
            return new_table

        jitted = _releasing(self._aot(
            jax.jit(shard_map(
                rehash_local, mesh=self._mesh, in_specs=P("shard"),
                out_specs=P("shard"), check_vma=False),
                donate_argnums=(0,)),
            (jax.ShapeDtypeStruct((self._n * old_cap,), jnp.uint64,
                                  sharding=self._shard_spec()),)))
        self._wave_cache[key] = jitted
        return jitted

    # -- Host orchestration ------------------------------------------------

    def _run_waves(self) -> None:
        """The pipelined adaptive host loop over per-shard arenas — the
        single-chip fused schedule (see ``FusedTpuBfsChecker``) with
        per-shard head/tail/occ rows in the chained stats array. Every
        dispatch exits at a collectively-agreed rest point, so chained
        speculative launches are no-ops past one, never hazards."""
        n = self._n
        F, W = self._F, self._Wrow  # storage row width (packed form)
        R_max = n * self._B_max * F
        properties = self._properties
        Pn = len(properties)
        L = SH_DISC + max(Pn, 1)

        # Split the pending blocks into per-shard seeds by ownership.
        blocks = list(self._pending)
        self._pending.clear()
        if blocks:
            all_vecs = np.concatenate([b[0] for b in blocks])
            all_fps = np.concatenate([b[1] for b in blocks])
            all_ebits = np.concatenate([b[2] for b in blocks])
        else:
            all_vecs = np.zeros((0, W), np.uint32)
            all_fps = np.zeros(0, np.uint64)
            all_ebits = np.zeros(0, np.uint32)
        assign_np = np.asarray(self._owner_map.assignment(), np.int64)
        owners = assign_np[(all_fps % np.uint64(n)).astype(np.int64)]
        seeds = [(all_vecs[owners == i], all_fps[owners == i],
                  all_ebits[owners == i]) for i in range(n)]
        max_seed = max((len(s[1]) for s in seeds), default=0)

        ucap = self._arena_capacity or max(1 << 14, 4 * R_max,
                                           _pow2(max_seed))
        ucap = max(_pow2(ucap), _pow2(max_seed))
        pad = _pow2(max(max_seed, 1))
        # Flat [n * pad] layout (shard-major) like the visited table.
        pv = np.zeros((n * pad, W), np.uint32)
        pf = np.full(n * pad, SENTINEL, np.uint64)
        pe = np.zeros(n * pad, np.uint32)
        tails = np.zeros(n, np.int64)
        for i, (sv, sf, se) in enumerate(seeds):
            k = len(sf)
            pv[i * pad:i * pad + k] = sv
            pf[i * pad:i * pad + k] = sf
            pe[i * pad:i * pad + k] = se
            tails[i] = k
        spec = self._shard_spec()
        vecs_a = self._grow_fn(pad, ucap, jnp.uint32, W)(
            jax.device_put(pv, spec))
        fps_a = self._grow_fn(pad, ucap, jnp.uint64)(
            jax.device_put(pf, spec))
        par_a = self._grow_fn(pad, ucap, jnp.uint64)(
            jax.device_put(np.full(n * pad, SENTINEL, np.uint64), spec))
        eb_a = self._grow_fn(pad, ucap, jnp.uint32)(
            jax.device_put(pe, spec))
        self._ucap = ucap
        disc = jnp.full((max(Pn, 1),), SENTINEL, jnp.uint64)
        visited = self._visited
        occs = np.array(self._seed_occ, np.int64)
        base_states = self._state_count
        target_eff = ((self._target_state_count - base_states)
                      if self._target_state_count is not None else 1 << 62)
        succ_total = 0
        cand_seen = 0  # candidates attributed to processed dispatches
        n_seed_rows = int(tails.sum())
        # Parent-log bookkeeping is per shard for this engine.
        self._shard_synced = tails.copy()
        self._shard_tails = tails.copy()
        self._shard_heads = np.zeros(n, np.int64)

        self.wave_log.append((time.monotonic(), self._state_count))
        self._arena = (vecs_a, fps_a, par_a, eb_a)
        arena_total = n_seed_rows
        last_ckpt_states = 0

        stats_np = np.zeros((n, L), np.int64)
        stats_np[:, ST_HEAD] = self._shard_heads
        stats_np[:, ST_TAIL] = self._shard_tails
        stats_np[:, ST_OCC] = occs
        stats_np[:, ST_SUCC] = succ_total   # replicated
        stats_np[:, ST_TARGET] = target_eff  # replicated
        stats_dev = jax.device_put(stats_np, self._shard_spec())

        from collections import deque
        # (stats_dev, meta, launch seconds), oldest first
        inflight: deque = deque()

        def process(entry) -> None:
            with self._tracer.span("fused.process"):
                apply(entry, time.monotonic())

        def apply(entry, t_proc: float) -> None:
            nonlocal occs, succ_total, cand_seen, arena_total
            if self._faults.active:
                # Same placement rationale as the single-chip fused
                # engine: before any bookkeeping, the torn-frontier
                # worst case.
                self._faults.crash("wave_crash", self._tracer,
                                   wave=len(self.dispatch_log))
            stats_out, meta, launch_s = entry
            t_wait = time.monotonic()
            with self._tracer.span("fused.stats_wait"):
                stats_h = np.asarray(stats_out)      # [n, L]
            waited = time.monotonic() - t_wait
            heads_prev = self._shard_heads
            heads = stats_h[:, ST_HEAD].copy()
            tails = stats_h[:, ST_TAIL].copy()
            occs = stats_h[:, ST_OCC].copy()
            succ_prev = succ_total
            succ_total = int(stats_h[0, ST_SUCC])
            cand_total = int(stats_h[0, ST_CAND])
            cand_prev, cand_seen = cand_seen, cand_total
            if stats_h[:, ST_ERR].any():
                lane = self._dm.error_lane
                raise RuntimeError(
                    f"device model error lane {lane} is set in a "
                    "generated state: an encoding capacity was exceeded "
                    "(for actor models: raise net_slots)")
            new_total = int(tails.sum())
            with self._lock:
                self._shard_heads = heads
                self._shard_tails = tails
                self._resident = int(occs.sum())  # device occupancy
                self._shard_occ = occs
                self._state_count = base_states + succ_total
                novel = new_total - arena_total
                self._unique_count += novel
                arena_total = new_total
                now = time.monotonic()
                self.wave_log.append((now, self._state_count))
                waves = int(stats_h[0, ST_WAVES])
                x_rounds = int(stats_h[0, ST_EXCHANGE_ROUNDS])
                cap = exchange_bucket_rows(meta["bucket"] * F, n)
                # Unified wave event (obs schema): deltas vs the last
                # processed dispatch; load factor is the fullest
                # shard's table slice (the growth-gating quantity).
                wave_evt = dict(
                    meta, t=now, states=self._state_count,
                    unique=self._unique_count,
                    waves=waves,
                    compiled=self._take_compile(),
                    successors=succ_total - succ_prev,
                    candidates=cand_total - cand_prev, novel=novel,
                    # v15: the slowest shard's loop rounds per wave,
                    # summed over the dispatch, and the host's own time
                    # on it (its launch and processing, less the stats
                    # wait).
                    probe_rounds=int(stats_h[0, ST_PROBE_ROUNDS]),
                    dedup_rounds=int(stats_h[0, ST_DEDUP_ROUNDS]),
                    # v17: the rows those rounds carried, each round
                    # over a chunk of the n*CAP rows a shard receives in
                    # an exchange round.
                    probe_slots=int(stats_h[0, ST_PROBE_ROUNDS])
                    * probe_chunk(n * cap),
                    host_s=launch_s + (now - t_proc) - waited,
                    # v16: successor rows sent to another shard, and the
                    # rows the all-to-alls carried between shards (each
                    # shard's n-1 off-shard buckets of CAP rows a round).
                    exchange_rows=int(stats_h[0, ST_EXCHANGE_ROWS]),
                    exchange_slots=x_rounds * n * (n - 1) * cap,
                    # v18: the exchange rounds the dispatch's waves took.
                    exchange_rounds=x_rounds,
                    # Frontier rows consumed across every shard (the
                    # kernel-occupancy numerator).
                    rows=int((heads - heads_prev).sum()),
                    out_rows=None, capacity=self._capacity,
                    load_factor=round(
                        int(occs.max()) / self._capacity, 4),
                    overflow=False,
                    # Bandwidth gauges (obs schema v2): per-shard arena
                    # and table slices, summed over the mesh.
                    bytes_per_state=4 * self._Wrow,
                    arena_bytes=n * ucap * (4 * self._Wrow + 8 + 8 + 4),
                    table_bytes=n * self._capacity * 8,
                    # v10: wave-loop host-I/O stall since the last
                    # wave event (safe-point joins + inline writes).
                    io_stall_s=self._take_io_stall(),
                    # v5 attribution: the ownership epoch this wave's
                    # routing was compiled against.
                    epoch=self._owner_map.epoch)
                if self._store.active:
                    # Tier occupancy gauges (obs schema v6).
                    wave_evt.update(
                        self._store.gauges(),
                        tier_device_rows=int(occs.sum()),
                        tier_device_bytes=n * ucap
                        * self._arena_row_bytes()
                        + n * self._capacity * 8)
                if self._prof.enabled:
                    # v13 cost stamping + (on sampled dispatches) the
                    # profile_snapshot roofline event; the internal
                    # riders never reach the dispatch log or trace.
                    self._prof.wave(
                        wave_evt, wave_evt.pop("_prof_key", None),
                        wave_evt.pop("_prof_s", None),
                        self._tracer, self._flight)
                self.dispatch_log.append(wave_evt)
                if self._flight.armed:
                    self._flight.record(wave_evt)
                if Pn:
                    disc_h = np.ascontiguousarray(
                        stats_h[0, SH_DISC:SH_DISC + Pn]).view(np.uint64)
                    for i, prop in enumerate(properties):
                        fp = int(disc_h[i])
                        if (fp != int(SENTINEL)
                                and prop.name not in self._discoveries):
                            self._discoveries[prop.name] = fp
            if self._tracer.enabled:
                self._tracer.wave(wave_evt)
            if self._wave_obs.enabled:
                self._wave_obs.wave(wave_evt, self._tracer, self._flight)
            self._service_sync(None)

        while True:
            if self._preempt_evt.is_set():
                # Preemption: the epilogue retires every in-flight
                # dispatch and syncs the parent log (see the single-chip
                # fused loop).
                self.preempted = True
                break
            with self._lock:
                # Vacuously true with zero properties (bfs.rs:117).
                done = (len(self._discoveries) == Pn
                        or (self._target_state_count is not None
                            and self._state_count
                            >= self._target_state_count))
            live = int((self._shard_tails - self._shard_heads).sum())
            if done or (live <= 0 and not inflight):
                break

            # Intended next bucket from the fullest shard's live rows.
            bucket = pick_bucket(
                self._buckets,
                int((self._shard_tails - self._shard_heads).max()))
            # A wave admits at most R_b rows and writes at most W_b past
            # a shard's tail (the dispatch's cond holds the same bounds).
            R_b = n * bucket * F
            W_b = exchange_window_rows(bucket * F, n)
            growth = (int(occs.max()) + R_b > self._capacity // 2
                      or int(self._shard_tails.max()) + W_b > ucap)
            ckpt_due = (self._ckpt_path is not None
                        and (self._unique_count - last_ckpt_states
                             >= self._ckpt_every * self._B))
            if (growth or ckpt_due or live <= 0) and inflight:
                process(inflight.popleft())
                continue
            if growth:
                with self._tracer.span("fused.grow"):
                    # Wrapped for OOM graceful degradation like the
                    # single-chip fused engine: shed the top batch bucket
                    # and re-evaluate at the loop top.
                    try:
                        self._grow_requested = (
                            self._capacity * 2 if int(occs.max()) + R_b
                            > self._capacity // 2 else self._capacity)
                        if self._faults.active:
                            self._faults.crash("grow_oom", self._tracer)
                        while int(occs.max()) + R_b > self._capacity // 2:
                            new_cap = self._capacity * 2
                            if self._tracer.enabled:
                                self._tracer.event(
                                    "grow", kind="table",
                                    old=self._capacity, new=new_cap)
                            visited = self._rehash_fn(self._capacity,
                                                      new_cap)(visited)
                            self._capacity = new_cap
                            self._visited = visited
                        while int(self._shard_tails.max()) + W_b > ucap:
                            budget = self._store.device_budget \
                                if self._store.active else None
                            over = (budget is not None
                                    and 2 * n * ucap * self._arena_row_bytes()
                                    + n * self._capacity * 8 > budget)
                            if over and int(self._shard_heads.max()) > 0:
                                # Per-shard arena-span spill (tiered
                                # store): parent-sync every shard, then
                                # shift each shard's live window down by
                                # its own head — headroom without growing
                                # past the device budget. Bit-identical:
                                # each shard's [head_i, tail_i) rows are
                                # unchanged, just re-based.
                                self._fetch_parents(None)
                                shifts = self._shard_heads.copy()
                                sh = jax.device_put(shifts.astype(np.int64),
                                                    self._shard_spec())
                                vecs_a = self._roll_fn(
                                    ucap, jnp.uint32, W)(vecs_a, sh)
                                fps_a = self._roll_fn(
                                    ucap, jnp.uint64)(fps_a, sh)
                                par_a = self._roll_fn(
                                    ucap, jnp.uint64)(par_a, sh)
                                eb_a = self._roll_fn(
                                    ucap, jnp.uint32)(eb_a, sh)
                                self._arena = (vecs_a, fps_a, par_a, eb_a)
                                with self._lock:
                                    self._shard_tails = \
                                        self._shard_tails - shifts
                                    self._shard_heads = np.zeros(
                                        n, np.int64)
                                    self._shard_synced = \
                                        self._shard_synced - shifts
                                rows = int(shifts.sum())
                                # Re-base the novel-count baseline: novel
                                # is the next dispatch's tails.sum() minus
                                # this, and every tail just moved down by
                                # its shard's shift.
                                arena_total -= rows
                                self._store.note_arena_span(
                                    rows, rows * self._arena_row_bytes())
                                # Rebuild the chained per-shard stats at
                                # rest (discovery slots are outputs only).
                                st = np.zeros((n, L), np.int64)
                                st[:, ST_HEAD] = 0
                                st[:, ST_TAIL] = self._shard_tails
                                st[:, ST_OCC] = occs
                                st[:, ST_SUCC] = succ_total
                                st[:, ST_CAND] = cand_seen
                                st[:, ST_TARGET] = target_eff
                                stats_dev = jax.device_put(
                                    st, self._shard_spec())
                                continue
                            if over and self._store.active:
                                self._store.note_device_pressure(
                                    2 * n * ucap * self._arena_row_bytes()
                                    + n * self._capacity * 8, budget)
                            new_ucap = ucap * 2
                            if self._tracer.enabled:
                                self._tracer.event("grow", kind="arena",
                                                   old=ucap, new=new_ucap)
                            vecs_a = self._grow_fn(
                                ucap, new_ucap, jnp.uint32, W)(vecs_a)
                            fps_a = self._grow_fn(
                                ucap, new_ucap, jnp.uint64)(fps_a)
                            par_a = self._grow_fn(
                                ucap, new_ucap, jnp.uint64)(par_a)
                            eb_a = self._grow_fn(
                                ucap, new_ucap, jnp.uint32)(eb_a)
                            ucap = new_ucap
                            self._ucap = ucap
                            self._slice_cache.clear()
                            self._arena = (vecs_a, fps_a, par_a, eb_a)
                    except Exception as e:  # noqa: BLE001 — non-OOM raised
                        self._handle_grow_failure(e)
                continue
            if ckpt_due:
                with self._tracer.span("fused.checkpoint"):
                    self._write_checkpoint(self._ckpt_path)
                last_ckpt_states = self._unique_count
                continue

            pkey = prof_s = t0 = None
            if self._prof.enabled:
                pkey = self._prof_key(
                    ("sharded-dispatch", bucket, self._capacity, ucap,
                     self._owner_map.epoch))
                if self._prof.should_sample(pkey):
                    t0 = time.monotonic()
            t_launch = time.monotonic()
            with self._tracer.span("fused.launch"):
                (vecs_a, fps_a, par_a, eb_a, visited, disc,
                 stats_dev) = self._dispatch_fn(
                    bucket, self._capacity, ucap)(
                    vecs_a, fps_a, par_a, eb_a, visited, disc, stats_dev)
            launch_s = time.monotonic() - t_launch
            if t0 is not None:
                # Rest-point timing (obs/prof.py): draining the
                # multi-dispatch pipeline for this one sample is the
                # 1/N price of a real device-time measurement.
                jax.block_until_ready(stats_dev)
                prof_s = time.monotonic() - t0
            self._arena = (vecs_a, fps_a, par_a, eb_a)
            self._visited = visited
            meta = {
                "bucket": bucket, "inflight": len(inflight) + 1,
                "kernel_path": "xla",
                "expand_impl": "step"}
            if pkey is not None:
                # Internal riders for process() — popped there before
                # the event reaches the schema'd streams.
                meta["_prof_key"] = pkey
                if prof_s is not None:
                    meta["_prof_s"] = prof_s
            inflight.append((stats_dev, meta, launch_s))
            if len(inflight) >= self._depth:
                process(inflight.popleft())
        # Retire every launched dispatch (normal exit); see the
        # single-chip fused loop for the rationale.
        while inflight:
            process(inflight.popleft())

        self._fetch_parents(None)

    def _reset_engine_state(self) -> None:
        super()._reset_engine_state()
        for attr in ("_shard_synced", "_shard_tails", "_shard_heads",
                     "_ucap"):
            self.__dict__.pop(attr, None)

    # -- Parent log / checkpoint (per-shard arenas) ------------------------

    def _arena_spans(self) -> list:
        u = self._ucap
        return [(i * u, int(h), int(t)) for i, (h, t) in enumerate(
            zip(self._shard_heads, self._shard_tails))]

    def _fetch_parents(self, _tail=None) -> None:
        if hasattr(self, "_arena") and any(
                self._shard_tails > self._shard_synced):
            with self._tracer.span("fused.parent_sync"):
                _, fps_a, par_a, _ = self._arena
                u = self._ucap
                for i in range(self._n):
                    lo = int(self._shard_synced[i])
                    hi = int(self._shard_tails[i])
                    if hi <= lo:
                        continue
                    child = self._fetch_rows(fps_a, i * u + lo, hi - lo)
                    parent = self._fetch_rows(par_a, i * u + lo, hi - lo)
                    with self._lock:
                        self._parent_log.append((child, parent))
                    self._shard_synced[i] = hi
        with self._sync_cond:
            self._sync_generation += 1
            self._sync_cond.notify_all()

    def _pending_blocks(self) -> list:
        if not hasattr(self, "_arena"):
            return list(self._pending)
        vecs_a, fps_a, _, eb_a = self._arena
        u = self._ucap
        blocks = []
        for i in range(self._n):
            lo = int(self._shard_heads[i])
            hi = int(self._shard_tails[i])
            if hi <= lo:
                continue
            blocks.append((
                self._fetch_rows(vecs_a, i * u + lo, hi - lo, self._Wrow),
                self._fetch_rows(fps_a, i * u + lo, hi - lo),
                self._fetch_rows(eb_a, i * u + lo, hi - lo)))
        return blocks

    def _write_checkpoint(self, path: str) -> None:
        from .engine import TpuBfsChecker

        if hasattr(self, "_arena"):
            self._fetch_parents(None)
        # Skip FusedTpuBfsChecker's override (single-arena bookkeeping);
        # the base writer consumes _pending_blocks/_parent_map, which
        # this class provides per shard.
        TpuBfsChecker._write_checkpoint(self, path)
