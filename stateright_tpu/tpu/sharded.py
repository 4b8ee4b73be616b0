"""Multi-chip BFS: fingerprint-sharded visited tables + ICI all-to-all.

The reference is a single-process checker; its only scale-out axis is a
work-stealing thread pool (`bfs.rs:29-30,70-74`). The TPU-native scale-out
replaces that with SPMD over a ``jax.sharding.Mesh``:

- **Ownership**: fingerprint space is hash-partitioned — device
  ``fp % n_shards`` owns a state. Each device holds the sorted visited
  table for *its* fingerprints only, so table capacity scales linearly
  with chips.
- **Wave shuffle**: every wave, each device expands its share of the
  frontier, fingerprints the successors, buckets them by owner, and a
  single ``lax.all_to_all`` (ICI when the mesh is a TPU slice, DCN across
  hosts) routes each successor to its owner, which dedups it against its
  local table. New states stay with their owner as its next-wave frontier
  share — ownership doubles as load balancing.
- **Parent pointers travel with the data**: each routed successor carries
  its parent's fingerprint and eventually-bits, so the host parent map
  (`bfs.rs:26`) needs no second exchange.

Everything inside the wave is one jitted ``shard_map`` program; the host
only feeds per-shard frontier batches and drains per-shard new-state
streams.

Like the reference's multithreaded BFS (`checker.rs:115-118`), discovery
paths are not guaranteed shortest when sharded: wave composition across
shard queues is not a global level order.
"""

from __future__ import annotations

import time
import warnings
from functools import partial
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ._compat import shard_map

from ..resilience.faults import ExchangeIntegrityError
from ..resilience.membership import EpochOwnership, OwnerMap
from .device_model import DeviceModel
from .engine import (TpuBfsChecker, compaction_order,
                     dedup_and_insert_counted, eval_properties,
                     expand_frontier, fingerprint_successors,
                     first_occurrence_candidates, host_table_insert,
                     pick_bucket, succ_bucket_ladder)
from .hashing import SENTINEL

__all__ = ["ShardedTpuBfsChecker"]


class ShardedTpuBfsChecker(EpochOwnership, TpuBfsChecker):
    """The multi-device wave engine. ``batch_size`` is per shard.

    The ``_ENGINE_ID`` class attribute tags this engine's wave events
    in the obs stream.

    ``exchange_novel_only`` (default on) runs the intra-wave local dedup
    on the SENDER side, before the all-to-all: only each shard's
    locally-novel candidates (first occurrence of each distinct
    fingerprint among its B*F successors) enter the exchange, so
    duplicate successors die in their producer's local pass instead of
    riding the interconnect to be discarded by the owner (the
    shared-hash-table observation of arXiv:1004.2772: thin the traffic
    INTO the global structure). Bit-identical: a dropped row is a
    same-shard later duplicate, which the owner-side first-occurrence
    rule — applied to the shard-major receive order — could never have
    selected anyway."""

    _ENGINE_ID = "sharded"

    def __init__(self, builder, batch_size: int = 512,
                 device_model: Optional[DeviceModel] = None,
                 table_capacity: int = 1 << 16,
                 mesh: Optional[Mesh] = None,
                 exchange_novel_only: Optional[bool] = None, **kwargs):
        if mesh is None:
            mesh = Mesh(np.array(jax.devices()), ("shard",))
        self._mesh = mesh
        self._n_shards = mesh.devices.size
        # Epoch-versioned ownership (resilience.membership): partition
        # ``fp % n`` normally lives on shard ``fp % n`` (the identity
        # map — device routing stays the raw modulo, zero overhead),
        # but the assignment can be remapped at a rest point
        # (``set_owner_assignment``), bumping the epoch; compiled wave
        # programs are keyed by it, so stale routing can never run.
        self._owner_map = OwnerMap.identity(self._n_shards)
        self._exchange_novel = (True if exchange_novel_only is None
                                else bool(exchange_novel_only))
        if kwargs.pop("pipeline", None):
            raise NotImplementedError(
                "the sharded engine's wave loop is not software-pipelined "
                "yet; drop pipeline=True (the all-to-all already overlaps "
                "per-shard work)")
        super().__init__(builder, batch_size=batch_size,
                         device_model=device_model,
                         table_capacity=table_capacity,
                         pipeline=False, **kwargs)

    def _pre_spawn_check(self) -> None:
        from ..model import Expectation

        for p, fn in zip(self._properties, self._prop_fns):
            if p.expectation is Expectation.EVENTUALLY and fn is None:
                raise NotImplementedError(
                    f"sharded engine requires a device predicate for "
                    f"eventually property {p.name!r} (per-path bits are "
                    "cleared on device before the all-to-all)")

    # -- Sharded state ----------------------------------------------------

    def _pending_blocks(self) -> list:
        """Frontier blocks across all shard queues (plus anything still
        in the pre-split queue, when the worker hasn't started);
        paged-out blocks materialize non-destructively."""
        from ..store.tiered import FrontierRef

        blocks = list(self._pending)
        for q in getattr(self, "_queues", []):
            blocks.extend(q)
        return [self._store.load_ref(b) if isinstance(b, FrontierRef)
                else b for b in blocks]

    def _new_table(self, fps) -> jax.Array:
        """Global [n_shards * capacity] table; each shard's slice is an
        open-addressing hash table over its owned fingerprints. Also
        (re)establishes ``_shard_counts`` — per-shard table occupancy,
        the quantity ``_needs_growth`` compares against capacity — so
        fresh runs, growth rehashes, and checkpoint resumes all account
        for every resident fingerprint."""
        n, cap = self._n_shards, self._capacity
        table = np.full((n, cap), SENTINEL, np.uint64)
        buckets: list = [[] for _ in range(n)]
        for fp in fps:
            buckets[self._owner(int(fp))].append(fp)
        for i, bucket in enumerate(buckets):
            host_table_insert(table[i], np.fromiter(
                (int(f) for f in bucket), np.uint64, len(bucket)))
        self._shard_counts = [len(b) for b in buckets]
        self._resident = sum(self._shard_counts)
        sharding = jax.sharding.NamedSharding(self._mesh, P("shard"))
        return jax.device_put(table.reshape(n * cap), sharding)

    def _resize_table(self) -> None:
        # The base _grow_table wraps this with the OOM graceful
        # degradation (grow_oom fault hook + batch-bucket shedding).
        real = np.asarray(self._visited)
        real = real[real != SENTINEL]
        old = self._capacity
        while self._needs_growth():
            self._capacity *= 2
        if self._tracer.enabled:
            self._tracer.event("grow", kind="table", old=old,
                               new=self._capacity)
        try:
            self._visited = self._new_table(real)
        except BaseException:
            self._capacity = old
            raise

    def _reset_engine_state(self) -> None:
        # restart_from support: stale per-shard queues from the failed
        # run must not leak into _pending_blocks before the restarted
        # worker re-splits the reloaded frontier.
        self.__dict__.pop("_queues", None)

    def _needs_growth_at(self, capacity: int) -> bool:
        """Capacity is per shard and a single wave can add up to
        ``n_shards * B * F`` states to ONE shard (every device's full
        fan-out routed to the same owner), so headroom is reserved
        against the fullest shard — and the open-addressing table wants
        load factor <= 1/2 so probe chains stay O(1)."""
        worst = max(self._shard_counts) if getattr(
            self, "_shard_counts", None) else 0
        return (worst + self._n_shards * self._B_max * self._F
                > capacity // 2)

    def _table_bytes(self, capacity: int) -> int:
        # Capacity is PER SHARD; the device footprint is the mesh's.
        return self._n_shards * capacity * 8

    def _spill_enough(self, keep_fps: np.ndarray) -> bool:
        """Per-shard growth predicate over the survivors: the fullest
        shard's KEPT rows must leave wave headroom at the current
        capacity."""
        if not len(keep_fps):
            worst = 0
        else:
            assign = np.asarray(self._owner_map.assignment(), np.int64)
            owners = assign[(np.asarray(keep_fps, np.uint64)
                             % np.uint64(self._n_shards)).astype(
                                 np.int64)]
            worst = int(np.bincount(
                owners, minlength=self._n_shards).max())
        return (worst + self._n_shards * self._B_max * self._F
                <= self._capacity // 2)

    # -- Sharded wave program ---------------------------------------------

    def _succ_full_rows(self, B: int) -> int:
        # A shard can receive every other shard's full fan-out.
        return self._n_shards * B * self._F

    def _route_fn(self, B: int):
        """Builds the sender side of the wave — expand, fingerprint,
        eventually-bit clearing, optional sender-side local dedup, and
        the all-to-all routing home. Shared by the wave program and the
        overflow regather (which re-runs it deterministically and lets
        XLA DCE the property/terminal outputs it does not use)."""
        dm = self._dm
        n = self._n_shards
        F, W = self._F, self._W
        Wr = self._Wrow
        layout = self._wave_layout()
        S = B * F          # successors per shard per wave
        CAP = S            # per-destination bucket capacity (worst case)
        R = n * CAP        # receive buffer rows per shard
        prop_fns = list(self._prop_fns)
        use_sym = self._use_symmetry
        exchange_novel = self._exchange_novel
        sentinel = jnp.uint64(SENTINEL)
        # Ownership assignment, baked into the compiled program (the
        # wave cache is epoch-keyed, so a remap recompiles). Identity
        # keeps the raw-modulo routing — the compiled HLO is unchanged
        # from the pre-epoch engine.
        assign = (None if self._owner_map.is_identity
                  else jnp.asarray(
                      np.asarray(self._owner_map.assignment(),
                                 np.int32)))
        from ..model import Expectation
        eventually_device = [
            i for i, p in enumerate(self._properties)
            if p.expectation is Expectation.EVENTUALLY]

        def route(vecs, fps, valid, ebits):
            # Local views: vecs [B, Wr] (storage row format), fps [B],
            # valid [B], ebits [B]. Unpack to real lanes for compute.
            if layout is not None:
                vecs = layout.unpack(vecs)
            conds = eval_properties(prop_fns, vecs)
            succ_flat, sflat, succ_count, terminal = expand_frontier(
                dm, vecs, valid)
            dedup_fps, path_fps = fingerprint_successors(
                dm, succ_flat, sflat, use_sym)
            parent_fps = jnp.repeat(fps, F)
            # Children inherit the parent's ebits *after* clearing bits for
            # eventually properties satisfied at the parent (bfs.rs:212-222)
            # — cleared here because the parent row is gone post-shuffle.
            ebits_cleared = ebits
            for i in eventually_device:
                ebits_cleared = ebits_cleared & ~jnp.where(
                    conds[i], jnp.uint32(1 << i), jnp.uint32(0))
            child_ebits = jnp.repeat(ebits_cleared, F)

            if exchange_novel:
                # Sender-side local dedup: only the first occurrence of
                # each distinct fingerprint enters the exchange. A
                # dropped row is a same-shard later duplicate the
                # owner's first-occurrence rule (over the shard-major
                # receive order) could never select, so the surviving
                # rows — and their relative order — are unchanged.
                send_mask = first_occurrence_candidates(dedup_fps)
            else:
                send_mask = sflat

            # Bucket successors by owner shard and all-to-all them home.
            part = (dedup_fps % n).astype(jnp.int32)
            dest = part if assign is None else assign[part]
            owner = jnp.where(send_mask, dest, n)
            order = jnp.argsort(owner, stable=True)
            so = owner[order]
            starts = jnp.searchsorted(so, jnp.arange(n + 1))
            rank = jnp.arange(S) - starts[jnp.clip(so, 0, n)]
            slot = so * CAP + rank  # >= n*CAP for the invalid bucket -> drop

            def scatter(x, fill):
                out = jnp.full((n * CAP,) + x.shape[1:], fill, x.dtype)
                return out.at[slot].set(x[order], mode="drop")

            # Pack BEFORE the exchange: only packed rows ride the
            # all-to-all (stacking on the novelty routing above — the
            # interconnect now moves Wr words per state, not W), and the
            # owner side never unpacks: received rows flow packed
            # through dedup compaction into its queue/arena.
            succ_store = (succ_flat if layout is None
                          else layout.pack(succ_flat))
            send_vecs = scatter(succ_store, 0).reshape(n, CAP, Wr)
            send_dedup = scatter(dedup_fps, sentinel).reshape(n, CAP)
            send_path = scatter(path_fps, sentinel).reshape(n, CAP)
            send_parent = scatter(parent_fps, sentinel).reshape(n, CAP)
            send_ebits = scatter(child_ebits, 0).reshape(n, CAP)

            a2a = partial(jax.lax.all_to_all, axis_name="shard",
                          split_axis=0, concat_axis=0, tiled=True)
            recv_vecs = a2a(send_vecs).reshape(R, Wr)
            recv_dedup = a2a(send_dedup).reshape(R)
            recv_path = a2a(send_path).reshape(R)
            recv_parent = a2a(send_parent).reshape(R)
            recv_ebits = a2a(send_ebits).reshape(R)
            return (conds, succ_count, terminal, recv_vecs, recv_dedup,
                    recv_path, recv_parent, recv_ebits)

        return route

    def _wave_fn(self, capacity: int, batch: Optional[int] = None,
                 out_rows: Optional[int] = None):
        B = self._B if batch is None else batch
        n = self._n_shards
        F, W = self._F, self._W
        R = n * B * F      # receive buffer rows per shard
        K = R if out_rows is None else min(max(1, int(out_rows)), R)
        key = (B, capacity, K, self._owner_map.epoch)
        cached = self._wave_cache.get(key)
        if cached is not None:
            return cached
        mesh = self._mesh
        prop_fns = list(self._prop_fns)
        route = self._route_fn(B)

        def wave_local(vecs, fps, valid, ebits, visited):
            (conds, succ_count, terminal, recv_vecs, recv_dedup,
             recv_path, recv_parent, recv_ebits) = route(
                vecs, fps, valid, ebits)

            # Owner-side dedup (cross-sender duplicates + revisits) +
            # insert against this shard's table slice, then the ladder's
            # K-row compaction; the full novelty mask and the overflow
            # flag ship so a truncated wave regathers losslessly.
            new_mask, new_count, cand_count, merged, _ = (
                dedup_and_insert_counted(recv_dedup, visited, capacity))
            comp = compaction_order(new_mask)[:K]
            new_vecs = recv_vecs[comp]
            new_fps = recv_path[comp]
            new_parent = recv_parent[comp]
            new_ebits = recv_ebits[comp]
            overflow = new_count > K
            conds_out = [c for c in conds if c is not None]
            return (conds_out, succ_count[None], cand_count[None],
                    terminal, new_count[None], new_vecs, new_fps,
                    new_parent, new_ebits, new_mask, overflow[None],
                    merged)

        n_conds = sum(1 for fn in prop_fns if fn is not None)
        sharded = shard_map(
            wave_local, mesh=mesh,
            in_specs=(P("shard"), P("shard"), P("shard"), P("shard"),
                      P("shard")),
            out_specs=([P("shard")] * n_conds, P("shard"), P("shard"),
                       P("shard"), P("shard"), P("shard"), P("shard"),
                       P("shard"), P("shard"), P("shard"), P("shard"),
                       P("shard")),
            check_vma=False)
        # Donate the batch arrays too (0-3): they are rebuilt host-side
        # every wave, so the device copies are dead after the expand —
        # XLA can reuse their pages for the receive buffers.
        jitted = jax.jit(sharded, donate_argnums=(0, 1, 2, 3, 4))
        spec = jax.sharding.NamedSharding(mesh, P("shard"))

        def sds(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=spec)

        jitted = self._aot(jitted, (
            sds((n * B, self._Wrow), jnp.uint32), sds((n * B,), jnp.uint64),
            sds((n * B,), jnp.bool_), sds((n * B,), jnp.uint32),
            sds((n * capacity,), jnp.uint64)))
        if self._prof.enabled:
            # Sharded wave programs bypass the shared program cache
            # (the ownership epoch keys them per instance), so static
            # cost capture (obs/prof.py) rides here instead of
            # _cached_program.
            self._prof.capture(self._prof_key(key), jitted)
        self._wave_cache[key] = jitted
        return jitted

    def _regather_fn(self, batch: int, out_rows: int):
        """Overflow recovery under ``shard_map``: re-runs the
        deterministic sender side (expand + fingerprint + exchange —
        the all-to-all routes the same rows to the same slots) and
        compacts with the wave's own per-shard novelty masks at a rung
        that fits. No table access; property outputs are DCE'd."""
        B = batch
        n = self._n_shards
        F, W = self._F, self._W
        R = n * B * F
        K = min(max(1, int(out_rows)), R)
        key = ("regather", B, K, self._owner_map.epoch)
        cached = self._wave_cache.get(key)
        if cached is not None:
            return cached
        route = self._route_fn(B)

        def regather_local(vecs, fps, valid, ebits, new_mask):
            (_conds, _succ, _term, recv_vecs, _recv_dedup, recv_path,
             recv_parent, recv_ebits) = route(vecs, fps, valid, ebits)
            comp = compaction_order(new_mask)[:K]
            return (recv_vecs[comp], recv_path[comp], recv_parent[comp],
                    recv_ebits[comp])

        sharded = shard_map(
            regather_local, mesh=self._mesh,
            in_specs=(P("shard"),) * 5,
            out_specs=(P("shard"),) * 4,
            check_vma=False)
        jitted = jax.jit(sharded)
        spec = jax.sharding.NamedSharding(self._mesh, P("shard"))

        def sds(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=spec)

        jitted = self._aot(jitted, (
            sds((n * B, self._Wrow), jnp.uint32), sds((n * B,), jnp.uint64),
            sds((n * B,), jnp.bool_), sds((n * B,), jnp.uint32),
            sds((n * R,), jnp.bool_)))
        self._wave_cache[key] = jitted
        return jitted

    def _inject_exchange_faults(self, shard_blocks: list) -> list:
        """Applies any armed all-to-all faults to the fetched shard
        blocks: ``a2a_short`` drops a block's tail row (a short
        delivery), ``a2a_corrupt`` overwrites a fingerprint with the
        sentinel (payload corruption). Both are then caught by the
        owner-side integrity check. A fault only fires when a nonempty
        block exists to damage, so every emitted ``fault`` event has an
        observable failure to pair with."""
        target = next((i for i, b in enumerate(shard_blocks)
                       if len(b[1])), None)
        if target is None:
            return shard_blocks
        if self._faults.fires("a2a_short", self._tracer, shard=target):
            vecs, fps, parents, ebits = shard_blocks[target]
            shard_blocks[target] = (vecs[:-1], fps[:-1], parents[:-1],
                                    ebits[:-1])
            # Re-pick: a one-row target is empty now, and the corrupt
            # fault below needs a row to damage.
            target = next((i for i, b in enumerate(shard_blocks)
                           if len(b[1])), None)
        if target is not None and self._faults.fires(
                "a2a_corrupt", self._tracer, shard=target):
            vecs, fps, parents, ebits = shard_blocks[target]
            fps = fps.copy()
            fps[-1] = np.uint64(SENTINEL)
            shard_blocks[target] = (vecs, fps, parents, ebits)
        return shard_blocks

    # -- Host orchestration -----------------------------------------------

    def _run_waves(self) -> None:
        from ..model import Expectation

        model = self._model
        n = self._n_shards
        F, W = self._F, self._W
        properties = self._properties
        eventually_idx = self._eventually_idx

        # Per-shard pending BLOCK queues, seeded by ownership.
        # (_shard_counts — table occupancy — was established by
        # _new_table; pending states are already resident there.)
        from collections import deque
        queues = [deque() for _ in range(n)]
        self._queues = queues
        assign_np = np.asarray(self._owner_map.assignment(), np.int64)
        while self._pending:
            vecs, fps, ebits = self._pending.popleft()
            owners = assign_np[(fps % np.uint64(n)).astype(np.int64)]
            for i in range(n):
                mask = owners == i
                k = int(mask.sum())
                if k:
                    queues[i].append((vecs[mask], fps[mask], ebits[mask]))

        self.wave_log.append((time.monotonic(), self._state_count))
        wave_index = 0
        while any(queues):
            wave_index += 1
            if (self._ckpt_path is not None
                    and wave_index % self._ckpt_every == 0):
                self._write_checkpoint(self._ckpt_path)  # safe point
            if self._faults.active:
                self._faults.crash("wave_crash", self._tracer,
                                   wave=wave_index)
            with self._lock:
                if len(self._discoveries) == len(properties):
                    return
                if (self._target_state_count is not None
                        and self._state_count >= self._target_state_count):
                    return
            if self._needs_growth():
                self._grow_table()

            # Adaptive width: the smallest ladder bucket covering the
            # fullest shard queue (results are bucket-independent; the
            # cross-B parity suite pins this).
            widest = 0
            for q in queues:
                rows = 0
                for blk in q:
                    rows += (blk.rows if hasattr(blk, "rows")
                             else len(blk[1]))
                    if rows >= self._B_max:
                        break
                widest = max(widest, rows)
            B = pick_bucket(self._buckets, widest)
            r_full = n * B * F   # receive rows per shard (worst case)
            K = self._pick_out_rows(B)

            batch_vecs = np.zeros((n * B, self._Wrow), np.uint32)
            batch_fps = np.zeros(n * B, np.uint64)
            batch_ebits = np.zeros(n * B, np.uint32)
            valid = np.zeros(n * B, bool)
            for i, q in enumerate(queues):
                parts, m = self._take_batch(q, B)
                row = i * B
                for vecs, fps, ebits in parts:
                    k = len(fps)
                    batch_vecs[row:row + k] = vecs
                    batch_fps[row:row + k] = fps
                    batch_ebits[row:row + k] = ebits
                    row += k
                valid[i * B:i * B + m] = True

            pkey = prof_s = t0 = None
            if self._prof.enabled:
                pkey = self._prof_key(
                    (B, self._capacity, K, self._owner_map.epoch))
                if self._prof.should_sample(pkey):
                    t0 = time.monotonic()
            with warnings.catch_warnings():
                # Batch-array donations that cannot alias an output are
                # still useful on HBM backends; the mismatch warning is
                # cosmetic.
                warnings.filterwarnings(
                    "ignore",
                    message="Some donated buffers were not usable")
                (conds_out, succ_count, cand_count, terminal, new_count,
                 new_vecs, new_fps, new_parent, new_ebits, new_mask,
                 overflow, self._visited) = \
                    self._wave_fn(self._capacity, B, K)(
                        jnp.asarray(batch_vecs), jnp.asarray(batch_fps),
                        jnp.asarray(valid), jnp.asarray(batch_ebits),
                        self._visited)
            if t0 is not None:
                # Rest-point timing (obs/prof.py): the sharded loop is
                # synchronous, so the join costs only what the host
                # reads below would have paid anyway.
                jax.block_until_ready(self._visited)
                prof_s = time.monotonic() - t0

            new_count = np.asarray(new_count)
            r_out = K
            overflowed = bool(np.asarray(overflow).any())
            if overflowed:
                # Some shard's novel set outgrew the output rung: the
                # table insertions are complete and each shard's full
                # novelty mask is an output, so regather losslessly at
                # a rung that fits the worst shard (logged).
                r_out = pick_bucket(succ_bucket_ladder(r_full),
                                    int(new_count.max()))
                (new_vecs, new_fps, new_parent, new_ebits) = \
                    self._regather_fn(B, r_out)(
                        jnp.asarray(batch_vecs), jnp.asarray(batch_fps),
                        jnp.asarray(valid), jnp.asarray(batch_ebits),
                        new_mask)
                if self._tracer.enabled:
                    self._tracer.event("overflow_redispatch", bucket=B,
                                       out_rows=r_out,
                                       novel=int(new_count.max()))

            conds = self._eval_host_conds(
                conds_out, batch_vecs, np.flatnonzero(valid))

            if self._visitor is not None:
                for row in np.flatnonzero(valid):
                    self._visitor.visit(
                        model, self._reconstruct_path(int(batch_fps[row])))

            terminal = np.asarray(terminal)
            # Slice each shard's surviving rows on device; only those rows
            # cross to the host (each shard's output block is r_out rows).
            # Slice lengths round up to powers of two so the number of
            # shape-specialized dispatch entries stays O(log r_out).
            shard_blocks = []
            for i in range(n):
                k = int(new_count[i])
                base = i * r_out
                kb = min(max(1, 1 << (k - 1).bit_length()) if k else 0,
                         r_out)
                block_vecs = np.asarray(new_vecs[base:base + kb])[:k]
                self._check_error_lane(block_vecs)
                shard_blocks.append((
                    block_vecs,
                    np.asarray(new_fps[base:base + kb])[:k],
                    np.asarray(new_parent[base:base + kb])[:k],
                    np.asarray(new_ebits[base:base + kb])[:k]))

            if self._faults.active:
                shard_blocks = self._inject_exchange_faults(shard_blocks)
            # Owner-side exchange integrity check (always on — the cost
            # is one length compare and one O(novel) sentinel scan per
            # shard): a short or corrupted all-to-all delivery must die
            # HERE with a diagnosis, not as a poisoned queue entry
            # whose subtree silently vanishes. The wave's table
            # insertions are already applied, so the raise tears the
            # in-memory frontier — the supervisor resumes from the last
            # checkpoint.
            for i, (_, fps_i, _, _) in enumerate(shard_blocks):
                k = int(new_count[i])
                if len(fps_i) != k:
                    raise ExchangeIntegrityError(
                        f"all-to-all delivered {len(fps_i)} rows to "
                        f"shard {i} where its dedup reported {k} novel "
                        "states (short exchange); resume from the last "
                        "checkpoint")
                if k and (fps_i == np.uint64(SENTINEL)).any():
                    raise ExchangeIntegrityError(
                        f"all-to-all delivered a sentinel fingerprint "
                        f"inside shard {i}'s novel block (corrupt "
                        "exchange payload); resume from the last "
                        "checkpoint")

            # Tiered store: the device tables only know their RESIDENT
            # rows — re-generated spilled states look novel on device
            # (and were re-admitted to their owner's table slice). The
            # batched probe against the warm/cold partitions filters
            # them out of counts/queues/parents; the DEVICE novel
            # counts still feed shard occupancy (the rows ARE back in
            # the tables).
            dev_novel = [int(new_count[i]) for i in range(n)]
            if self._store.active and self._store.spilled_rows:
                filtered = []
                for vecs_i, fps_i, parents_i, ebits_i in shard_blocks:
                    if len(fps_i):
                        present = self._store.probe(
                            self._store_probe_fps(vecs_i, fps_i))
                        if present.any():
                            keep = ~present
                            vecs_i, fps_i, parents_i, ebits_i = (
                                vecs_i[keep], fps_i[keep],
                                parents_i[keep], ebits_i[keep])
                    filtered.append((vecs_i, fps_i, parents_i, ebits_i))
                shard_blocks = filtered

            with self._lock:
                succ_sum = int(np.asarray(succ_count).sum())
                cand_sum = int(np.asarray(cand_count).sum())
                self._state_count += succ_sum
                self._succ_hist.append((B, int(new_count.max())))
                self._resident += sum(dev_novel)
                # Stream each shard's new block into its queue + the
                # parent log FIRST so the wave event reports post-wave
                # occupancy (all array ops; bfs.rs:262 enqueue).
                novel_sum = 0
                for i, (vecs_i, fps_i, parents_i, ebits_i) \
                        in enumerate(shard_blocks):
                    self._shard_counts[i] += dev_novel[i]
                    k = len(fps_i)
                    if not k:
                        continue
                    self._unique_count += k
                    novel_sum += k
                    self._parent_log.append((fps_i, parents_i))
                    queues[i].append((vecs_i, fps_i, ebits_i))
                now = time.monotonic()
                self.wave_log.append((now, self._state_count))
                # Unified wave event (obs schema); load factor is the
                # FULLEST shard's slice — the quantity growth gates on.
                entry = {
                    "t": now, "states": self._state_count,
                    "unique": self._unique_count, "bucket": B,
                    "compiled": self._take_compile(), "waves": 1,
                    "inflight": 0, "out_rows": r_out,
                    # Valid frontier rows across all shard slots (the
                    # kernel-occupancy numerator; padded rows = n*B)
                    # and the successor-path implementation this
                    # dispatch ran.
                    "rows": int(valid.sum()),
                    "kernel_path": "xla",
                    "expand_impl": "step",
                    "successors": succ_sum, "candidates": cand_sum,
                    "novel": novel_sum, "capacity": self._capacity,
                    "load_factor": round(
                        max(self._shard_counts) / self._capacity, 4),
                    "overflow": overflowed,
                    # Bandwidth gauges (obs schema v2): capacity is per
                    # shard, so table bytes scale with the mesh; the
                    # unfused engine keeps its frontier host-side.
                    "bytes_per_state": 4 * self._Wrow,
                    "arena_bytes": None,
                    "table_bytes": n * self._capacity * 8,
                    # v10: wave-loop host-I/O stall since the last
                    # wave event (safe-point joins + inline writes).
                    "io_stall_s": self._take_io_stall(),
                    # v5 attribution: single-process sharded runs still
                    # record which ownership epoch the wave ran under
                    # (remaps bump it — resilience/membership.py).
                    "epoch": self._owner_map.epoch}
                if self._store.active:
                    # Tier occupancy gauges (obs schema v6).
                    entry.update(
                        self._store.gauges(),
                        tier_device_rows=self._resident,
                        tier_device_bytes=self._table_bytes(
                            self._capacity))
                if self._prof.enabled:
                    # v13 cost stamping + (on sampled dispatches) the
                    # profile_snapshot roofline event.
                    self._prof.wave(entry, pkey, prof_s, self._tracer,
                                    self._flight)
                self.dispatch_log.append(entry)
                if self._flight.armed:
                    self._flight.record(entry)
                for i, prop in enumerate(properties):
                    if prop.name in self._discoveries:
                        continue
                    if prop.expectation is Expectation.ALWAYS:
                        hits = valid & ~conds[i]
                    elif prop.expectation is Expectation.SOMETIMES:
                        hits = valid & conds[i]
                    else:
                        continue
                    rows = np.flatnonzero(hits)
                    if rows.size:
                        self._discoveries[prop.name] = int(batch_fps[rows[0]])
                ebits_after = batch_ebits.copy()
                for i in eventually_idx:
                    ebits_after &= ~np.where(
                        conds[i], np.uint32(1 << i), np.uint32(0))
                for row in np.flatnonzero(
                        terminal & valid & (ebits_after != 0)):
                    for i in eventually_idx:
                        prop = properties[i]
                        if (ebits_after[row] >> i) & 1 \
                                and prop.name not in self._discoveries:
                            self._discoveries[prop.name] = int(batch_fps[row])
            if self._store.active and novel_sum:
                # Host-tier frontier budget across every shard queue.
                self._store.balance_frontier(queues)
            if self._tracer.enabled:
                self._tracer.wave(entry)
            if self._wave_obs.enabled:
                self._wave_obs.wave(entry, self._tracer, self._flight)
