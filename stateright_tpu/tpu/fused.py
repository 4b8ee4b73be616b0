"""Fused device-queue BFS: the whole checker state lives on device.

``TpuBfsChecker`` keeps the frontier queue and parent map on the host, so
every wave pays two state-tensor transfers (batch up, survivors down) plus
several dispatch round trips. In the one classic-engine chip reading
(round 3, over a remote link) that host boundary dominated wall time
(~0.9 s/wave against ~0.4 s of device compute on the paxos bench
config). This engine removes the boundary entirely:

- **Arena**: every discovered state lives in a device-resident append-only
  arena — ``vecs[U, W]``, ``fps[U]``, ``parent fps[U]``, ``ebits[U]``.
  Rows ``[head, tail)`` are the not-yet-expanded BFS frontier, so the
  arena *is* the queue (FIFO ⇒ level order, like the pending queue of
  `bfs.rs:70-74`), *is* the parent map (`bfs.rs:26`), and *is* the
  checkpoint payload. Appends are one ``dynamic_update_slice`` per wave —
  contiguous, no scatter.
- **Fused waves**: one dispatch runs up to ``waves_per_dispatch`` BFS
  waves in a ``lax.while_loop``; property discoveries are resolved on
  device (first-hit fingerprint per property, in frontier order — the
  dedup/queue order of `bfs.rs:196-226,245-262`), so the host uploads
  nothing and downloads one packed stats vector per dispatch.
- **Lazy parent fetch**: ``(fp, parent fp)`` rows cross to the host only
  when a path is actually reconstructed (discoveries, checkpoint) —
  16 bytes per unique state, once, instead of per wave.

Growth (visited table or arena full) and checkpoints happen between
dispatches; the table rehash runs on device (old table entries re-probed
into a table of twice the capacity), so the resident set never crosses
the host boundary.

Semantics are bit-identical to ``TpuBfsChecker`` (same wave composition,
same dedup-order rule, same eventually-bits handling incl. the documented
revisit caveats of `bfs.rs:239-259`); the parity suite runs both.
"""

from __future__ import annotations

import threading
import warnings
import time
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..model import Expectation
from .engine import (TpuBfsChecker, compaction_order, dedup_and_insert,
                     dedup_and_insert_counted, eval_properties,
                     expand_frontier, fingerprint_successors, pick_bucket,
                     probe_chunk)
from .hashing import SENTINEL

__all__ = ["FusedTpuBfsChecker", "FusedUnsupported"]

# Dispatch-stats vector layout (int64). The SAME layout is consumed and
# produced by every dispatch program, so a dispatch can be launched
# directly from its predecessor's still-device-resident stats — the
# host only materializes a stats vector when it processes that dispatch
# (possibly one or more launches later). ``WAVES`` is reset per
# dispatch; ``TARGET`` rides along unchanged; ``CAND`` accumulates the
# distinct candidates that reached the global probe (the local-dedup
# collapse telemetry); ``PROBE_ROUNDS``/``DEDUP_ROUNDS`` are the rounds
# the dispatch's table-probe and local-dedup loops ran, summed over its
# waves (per dispatch, like ``WAVES``); discovery fingerprints are
# bitcast into the tail slots (they also travel as a separate donated
# array between dispatches).
(ST_HEAD, ST_TAIL, ST_OCC, ST_SUCC, ST_CAND, ST_TARGET, ST_ERR,
 ST_WAVES, ST_PROBE_ROUNDS, ST_DEDUP_ROUNDS) = range(10)
ST_DISC = 10


class FusedUnsupported(TypeError):
    """The model/builder needs a host-side per-wave hook; use the classic
    engine (``spawn_tpu_bfs(fused=False)``)."""


def _pow2(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length()


def _releasing(fn):
    """Wraps a jitted grow/rehash program so growth never retains the
    pre-growth buffer: the input is donated (backends that can alias or
    reuse its pages do), the cosmetic "donated buffers were not usable"
    warning is silenced where the shape change makes aliasing impossible,
    and the old buffer is explicitly deleted once the program has
    consumed it — peak memory during a doubling is the one unavoidable
    copy, not old + new + scratch."""
    def call(arr):
        with warnings.catch_warnings():
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            out = fn(arr)
        if isinstance(arr, jax.Array) and not arr.is_deleted():
            # Deleting an input of a still-in-flight async program frees
            # it under the reader (observed as garbage fingerprints in
            # the visited table on the CPU client); growth is a rest
            # point, so waiting out the copy costs nothing.
            jax.block_until_ready(out)
            arr.delete()
        return out
    return call


class FusedTpuBfsChecker(TpuBfsChecker):
    """Device-arena BFS with multi-wave dispatches."""

    _ENGINE_ID = "fused"

    # The fused engines dedup entirely on device across multi-wave
    # dispatches: a host-side probe of spilled visited partitions would
    # come too late (re-admitted rows would already be re-expanded into
    # the arena), so the tiered store must not evict from their tables.
    # Their device relief valve is the ARENA-SPAN spill instead: rows
    # [0, head) are the already-expanded prefix — the wave only ever
    # reads [head, tail) and the parent log is the rows' host-RAM home
    # — so under a device byte budget the prefix is parent-synced to
    # the host and the live window shifted down, freeing arena headroom
    # without growing (see _run_waves).
    _VISITED_SPILL_CAPABLE = False

    # No per-wave host boundary: frontiers, stats, and the dedup all
    # live in the donated device arena across a multi-wave dispatch, so
    # there is no point at which a wave's outputs could be split per
    # tenant — fused jobs run solo and share only compiled programs
    # (the jit cache), never dispatches (service/mux.py checks this).
    _MUX_CAPABLE = False

    # The fused wave appends to the donated arena through a full-window
    # dynamic_update_slice on purpose (narrowing it breaks XLA's
    # in-place aliasing — see the wave body), and its outputs never
    # cross the host boundary, so the successor output ladder has
    # nothing to bound here. Local dedup still runs (inside
    # dedup_and_insert_counted), and its collapse telemetry rides the
    # ST_CAND slot.
    _SUCC_LADDER_CAPABLE = False

    def __init__(self, builder, batch_size: int = 1024,
                 waves_per_dispatch: Optional[int] = None,
                 arena_capacity: Optional[int] = None,
                 inflight_dispatches: int = 2, **kwargs):
        kwargs.pop("pipeline", None)  # per-wave pipelining is subsumed
        if waves_per_dispatch is None:
            # One dispatch round trip per 16 waves; the loop exits early
            # on a drained queue / completed discoveries / growth, so a
            # large cap costs small models nothing (measured fastest on
            # the CPU backend too).
            waves_per_dispatch = 16
        self._K = max(1, int(waves_per_dispatch))
        self._arena_capacity = arena_capacity
        # Dispatch pipeline depth: how many dispatches may be launched
        # before the oldest one's stats are read back. Depth 2 keeps one
        # dispatch in flight while the host processes its predecessor;
        # depth 1 is the synchronous round-trip-per-dispatch schedule.
        # Safe at any depth: every dispatch re-checks its stop
        # predicates on device before expanding a wave, so a dispatch
        # launched past a rest point (growth due, queue drained, all
        # discovered) is a no-op, not a hazard.
        self._depth = max(1, int(inflight_dispatches))
        super().__init__(builder, batch_size=batch_size, pipeline=False,
                         **kwargs)

    def _check_support(self) -> None:
        if self._visitor is not None:
            raise FusedUnsupported(
                "visitors need the per-wave host loop; the builder falls "
                "back to the classic engine")
        if any(fn is None for fn in self._prop_fns):
            raise FusedUnsupported(
                "host-fallback properties need the per-wave host loop; "
                "the builder falls back to the classic engine")

    def _pre_spawn_check(self) -> None:
        # Worker/device-state handshake (parent fetches are worker-only;
        # other threads request one via the condition).
        self._sync_cond = threading.Condition()
        self._sync_requested = False
        self._sync_generation = 0
        self._synced_rows = 0  # arena rows already in the parent log
        self._slice_cache: dict = {}

    # -- Dispatch program --------------------------------------------------

    def _dispatch_fn(self, batch: int, capacity: int, ucap: int):
        # The shared-cache key carries the fused schedule knob K too:
        # two jobs share a dispatch program only when their wave
        # cadence agrees (engine id / packing / symmetry ride in
        # _cached_program's shared prefix).
        return self._cached_program(
            ("dispatch", batch, capacity, ucap, self._K),
            lambda: self._build_dispatch_fn(batch, capacity, ucap))

    def _build_dispatch_fn(self, batch: int, capacity: int, ucap: int):
        dm = self._dm
        B, F, W, K = batch, self._F, self._W, self._K
        Wr = self._Wrow
        layout = self._wave_layout()
        S = B * F
        prop_fns = list(self._prop_fns)
        use_sym = self._use_symmetry
        properties = self._properties
        P = len(properties)
        sentinel = jnp.uint64(SENTINEL)
        err_lane = dm.error_lane
        ebits_masks = [jnp.uint32(1 << i) for i in range(P)]

        def first_hit(disc_i, hit, bfps):
            """Keeps the first (frontier-order) hit's fingerprint, set
            exactly once across the whole run (bfs.rs:196-211)."""
            row = jnp.argmax(hit)  # first True
            fp = bfps[row]
            return jnp.where((disc_i == sentinel) & hit.any(), fp, disc_i)

        def wave(carry):
            (vecs_a, fps_a, par_a, eb_a, visited, head, tail, occ,
             succ_total, cand_total, err, disc, waves, rounds) = carry
            with jax.named_scope("load"):
                idx = head + jnp.arange(B, dtype=jnp.int64)
                valid = idx < tail
                idx_c = jnp.minimum(idx, ucap - 1)
                # The arena stores PACKED rows; unpack the batch to real
                # lanes at wave start (compute is layout-independent).
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
                disc = disc.at[i].set(first_hit(disc[i], hit, bfps))

            succ_flat, sflat, succ_count, terminal = expand_frontier(
                dm, bvecs, valid)
            dedup_fps, path_fps = fingerprint_successors(
                dm, succ_flat, sflat, use_sym)
            new_mask, new_count, cand_count, visited, wave_rounds = (
                dedup_and_insert_counted(dedup_fps, visited, capacity))

            # Eventually bits: clear satisfied at the parent, then flag
            # terminal parents with leftover bits (bfs.rs:212-226,265-272).
            cleared = bebits
            for i, prop in enumerate(properties):
                if prop.expectation is Expectation.EVENTUALLY:
                    cleared = cleared & ~jnp.where(
                        conds[i], ebits_masks[i], jnp.uint32(0))
            for i, prop in enumerate(properties):
                if prop.expectation is Expectation.EVENTUALLY:
                    hit = valid & terminal & ((cleared >> i) & 1  # noqa: E501
                                              ).astype(bool)
                    disc = disc.at[i].set(first_hit(disc[i], hit, bfps))

            # Append the survivors at the arena tail (frontier order —
            # the bfs.rs:262 enqueue order). Rows past new_count are
            # garbage beyond tail: overwritten by the next wave, never
            # read (all reads mask by tail). The append window is the
            # full S rows on purpose: narrowing it behind a lax.cond
            # breaks XLA's in-place aliasing of the donated arena and
            # forces whole-arena copies per wave (measured ~2x wall on
            # the CPU backend), which dwarfs the bytes saved.
            with jax.named_scope("store"):
                comp = compaction_order(new_mask)
                parent_rows = comp // F
                new_vecs = succ_flat[comp]
                new_fps = path_fps[comp]
                new_parent = bfps[parent_rows]
                new_ebits = cleared[parent_rows]
                if err_lane is not None:
                    err = err | jnp.any((new_vecs[:, err_lane] != 0)
                                        & (jnp.arange(S) < new_count))
                if layout is not None:
                    new_vecs = layout.pack(new_vecs)
                start = (tail,)
                vecs_a = jax.lax.dynamic_update_slice(
                    vecs_a, new_vecs, (tail, jnp.int64(0)))
                fps_a = jax.lax.dynamic_update_slice(fps_a, new_fps, start)
                par_a = jax.lax.dynamic_update_slice(par_a, new_parent,
                                                     start)
                eb_a = jax.lax.dynamic_update_slice(eb_a, new_ebits, start)

            nc = new_count.astype(jnp.int64)
            return (vecs_a, fps_a, par_a, eb_a, visited,
                    jnp.minimum(head + B, tail), tail + nc, occ + nc,
                    succ_total + succ_count,
                    cand_total + cand_count.astype(jnp.int64), err, disc,
                    waves + 1,
                    tuple(r + w for r, w in zip(rounds, wave_rounds)))

        def cond(carry):
            (_, _, _, _, _, head, tail, occ, succ_total, _cand, err,
             disc, waves, _rounds, target) = carry
            more = (waves < K) & (head < tail) & ~err
            more = more & (tail + S <= ucap)
            more = more & (occ + S <= capacity // 2)
            if P:
                more = more & ~jnp.all(disc != sentinel)
            # target is dynamic (carried): this run's successor budget.
            return more & (succ_total < target)

        def wave_t(carry):
            return wave(carry[:-1]) + (carry[-1],)

        def dispatch(vecs_a, fps_a, par_a, eb_a, visited, disc, stats_in):
            # stats_in/stats_out share the ST_* layout, so a successor
            # dispatch chains on this one's device-resident outputs
            # without a host round trip (the pipelined schedule).
            head, tail, occ, succ_total, cand_total, target = (
                stats_in[i] for i in (ST_HEAD, ST_TAIL, ST_OCC,
                                      ST_SUCC, ST_CAND, ST_TARGET))
            carry = (vecs_a, fps_a, par_a, eb_a, visited, head, tail, occ,
                     succ_total, cand_total, stats_in[ST_ERR] != 0, disc,
                     jnp.zeros((), jnp.int64), (jnp.int32(0),) * 2, target)
            (vecs_a, fps_a, par_a, eb_a, visited, head, tail, occ,
             succ_total, cand_total, err, disc, waves, rounds,
             _) = jax.lax.while_loop(cond, wave_t, carry)
            # Discovery slots ride in the stats vector (bitcast, so the
            # SENTINEL survives) — one host fetch per dispatch, not two.
            local_rounds, probe_rounds = (r.astype(jnp.int64)
                                          for r in rounds)
            stats = jnp.concatenate([
                jnp.stack([head, tail, occ, succ_total, cand_total,
                           target, err.astype(jnp.int64), waves,
                           probe_rounds, local_rounds]),
                jax.lax.bitcast_convert_type(disc, jnp.int64)])
            return vecs_a, fps_a, par_a, eb_a, visited, disc, stats

        # stats_in is NOT donated: the host reads dispatch k's stats
        # after dispatch k+1 (which consumes them as input) has launched.
        jitted = jax.jit(dispatch, donate_argnums=(0, 1, 2, 3, 4, 5))
        sds = jax.ShapeDtypeStruct
        jitted = self._aot(jitted, (
            sds((ucap, Wr), jnp.uint32), sds((ucap,), jnp.uint64),
            sds((ucap,), jnp.uint64), sds((ucap,), jnp.uint32),
            sds((capacity,), jnp.uint64), sds((max(P, 1),), jnp.uint64),
            sds((ST_DISC + max(P, 1),), jnp.int64)))
        return jitted

    def _grow_fn(self, old_cap: int, new_cap: int, dtype, width: int = 0):
        key = ("grow", old_cap, new_cap, str(dtype), width)
        cached = self._wave_cache.get(key)
        if cached is not None:
            return cached

        def grow(arr):
            shape = (new_cap, width) if width else (new_cap,)
            fill = SENTINEL if arr.dtype == jnp.uint64 else 0
            out = jnp.full(shape, fill, arr.dtype)
            start = (0, 0) if width else (0,)
            return jax.lax.dynamic_update_slice(out, arr, start)

        shape = (old_cap, width) if width else (old_cap,)
        jitted = _releasing(self._aot(
            jax.jit(grow, donate_argnums=(0,)),
            (jax.ShapeDtypeStruct(shape, dtype),)))
        self._wave_cache[key] = jitted
        return jitted

    def _rehash_fn(self, old_cap: int, new_cap: int):
        key = ("rehash", old_cap, new_cap)
        cached = self._wave_cache.get(key)
        if cached is not None:
            return cached

        def rehash(old_table):
            new_table = jnp.full((new_cap,), SENTINEL, jnp.uint64)
            _, _, new_table = dedup_and_insert(old_table, new_table,
                                               new_cap)
            return new_table

        jitted = _releasing(self._aot(
            jax.jit(rehash, donate_argnums=(0,)),
            (jax.ShapeDtypeStruct((old_cap,), jnp.uint64),)))
        self._wave_cache[key] = jitted
        return jitted

    def _roll_fn(self, ucap: int, dtype, width: int = 0):
        """The arena-span shift program: moves rows [shift, ucap) down
        to 0 (``jnp.roll`` — the wrapped-around prefix lands beyond
        ``tail`` where no read ever looks). Donated, so backends alias
        in place."""
        key = ("roll", ucap, str(dtype), width)
        cached = self._wave_cache.get(key)
        if cached is not None:
            return cached

        def roll(arr, shift):
            return jnp.roll(arr, -shift, axis=0)

        shape = (ucap, width) if width else (ucap,)
        jitted = self._aot(
            jax.jit(roll, donate_argnums=(0,)),
            (jax.ShapeDtypeStruct(shape, dtype),
             jax.ShapeDtypeStruct((), jnp.int64)))
        self._wave_cache[key] = jitted
        return jitted

    def _arena_row_bytes(self) -> int:
        """Device bytes per arena row (packed vec words + fp + parent
        fp + ebits)."""
        return 4 * self._Wrow + 8 + 8 + 4

    def _fetch_rows(self, arr, start: int, count: int,
                    width: int = 0) -> np.ndarray:
        """Device-slice [start, start+count) with O(log U) compiled
        shapes (power-of-two lengths, dynamic start)."""
        if count <= 0:
            shape = (0, width) if width else (0,)
            return np.zeros(shape, arr.dtype)
        ucap = arr.shape[0]
        kb = min(_pow2(count), ucap)
        key = ("slice", ucap, kb, str(arr.dtype), width)
        fn = self._slice_cache.get(key)
        if fn is None:
            size = (kb, width) if width else (kb,)

            def slice_fn(a, s):
                starts = (s, jnp.int64(0)) if width else (s,)
                return jax.lax.dynamic_slice(a, starts, size)

            fn = jax.jit(slice_fn)
            self._slice_cache[key] = fn
        clamped = min(start, ucap - kb)  # dynamic_slice clamps the same
        off = start - clamped
        return np.asarray(fn(arr, jnp.int64(clamped)))[off:off + count]

    # -- Host orchestration ------------------------------------------------

    def _run_waves(self) -> None:
        """The pipelined adaptive host loop.

        Every dispatch runs to a *true rest point* on device (queue
        drained, wave cap, all discovered, target met, error, or — the
        key ones — table/arena headroom exhausted), so the host can
        launch dispatch k+1 directly from k's device-resident carry
        BEFORE reading k's stats: a dispatch launched past a rest point
        re-checks the same predicates on device and no-ops. The host
        therefore keeps up to ``inflight_dispatches`` launches ahead of
        its stats reads, and only truly blocks at rest points that need
        host action (growth, checkpoints, discovery retirement).

        Batch width is re-picked per launch from the last *processed*
        frontier width over the bucket ladder — a stale estimate is a
        performance wrinkle, never a correctness one (results are
        bucket-independent; the cross-B parity suite pins this)."""
        F, W = self._F, self._Wrow  # storage row width (packed form)
        properties = self._properties
        P = len(properties)
        L = ST_DISC + max(P, 1)

        # Seed the arena from the pending blocks (fresh init states, or a
        # checkpoint's frontier). Parents of these rows are already known
        # host-side; only rows beyond _synced_rows are fetched later.
        blocks = list(self._pending)
        self._pending.clear()
        if blocks:
            seed_vecs = np.concatenate([b[0] for b in blocks])
            seed_fps = np.concatenate([b[1] for b in blocks])
            seed_ebits = np.concatenate([b[2] for b in blocks])
        else:
            seed_vecs = np.zeros((0, W), np.uint32)
            seed_fps = np.zeros(0, np.uint64)
            seed_ebits = np.zeros(0, np.uint32)
        n_seed = len(seed_fps)
        self._synced_rows = n_seed
        ucap = self._arena_capacity or max(1 << 15, 4 * self._B_max * F,
                                           _pow2(n_seed))
        ucap = _pow2(ucap)

        # Device state. The arena is built with on-device fills — only
        # the seed rows cross the boundary.
        pad = _pow2(max(n_seed, 1))
        ucap = max(ucap, pad)  # an explicit arena_capacity never truncates
                               # a resumed frontier
        pv = np.zeros((pad, W), np.uint32)
        pf = np.full(pad, SENTINEL, np.uint64)
        pe = np.zeros(pad, np.uint32)
        pv[:n_seed] = seed_vecs
        pf[:n_seed] = seed_fps
        pe[:n_seed] = seed_ebits
        vecs_a = self._grow_fn(pad, ucap, jnp.uint32, W)(jnp.asarray(pv))
        fps_a = self._grow_fn(pad, ucap, jnp.uint64)(jnp.asarray(pf))
        par_a = self._grow_fn(pad, ucap, jnp.uint64)(
            jnp.full(pad, SENTINEL, jnp.uint64))
        eb_a = self._grow_fn(pad, ucap, jnp.uint32)(jnp.asarray(pe))
        disc = jnp.full((max(P, 1),), SENTINEL, jnp.uint64)
        visited = self._visited
        # occupancy of the visited table (== arena rows unless resuming,
        # where the table also holds already-expanded states).
        occ = self._unique_count
        head, tail = 0, n_seed
        base_states = self._state_count
        # This run's successor budget (the target counts cumulative
        # state_count, which starts at base_states on resume).
        target_eff = ((self._target_state_count - base_states)
                      if self._target_state_count is not None else 1 << 62)
        succ_total = 0
        cand_seen = 0  # candidates attributed to processed dispatches

        self.wave_log.append((time.monotonic(), self._state_count))
        self._arena = (vecs_a, fps_a, par_a, eb_a)
        self._arena_tail = tail
        self._head = head
        last_ckpt_states = 0

        stats_np = np.zeros(L, np.int64)
        stats_np[ST_HEAD], stats_np[ST_TAIL] = head, tail
        stats_np[ST_OCC], stats_np[ST_SUCC] = occ, succ_total
        stats_np[ST_TARGET] = target_eff
        stats_dev = jnp.asarray(stats_np)

        from collections import deque
        # (stats_dev, meta, launch seconds), oldest first
        inflight: deque = deque()

        def process(entry) -> None:
            """Materializes one dispatch's stats (the only blocking
            read) and applies them; absolute values make processing a
            no-op dispatch harmless."""
            with self._tracer.span("fused.process"):
                apply(entry, time.monotonic())

        def apply(entry, t_proc: float) -> None:
            nonlocal head, tail, occ, succ_total, cand_seen
            if self._faults.active:
                # Before any count/arena bookkeeping: the dispatch's
                # table/arena mutations are device-resident and real, so
                # a crash here tears the in-memory frontier — only a
                # checkpoint resume repairs it.
                self._faults.crash("wave_crash", self._tracer,
                                   wave=len(self.dispatch_log))
            stats_out, meta, launch_s = entry
            t_wait = time.monotonic()
            with self._tracer.span("fused.stats_wait"):
                stats_h = np.asarray(stats_out)
            waited = time.monotonic() - t_wait
            succ_prev = succ_total
            head_prev = head
            head, tail, occ, succ_total = (
                int(stats_h[i]) for i in (ST_HEAD, ST_TAIL, ST_OCC,
                                          ST_SUCC))
            cand_total = int(stats_h[ST_CAND])
            cand_prev, cand_seen = cand_seen, cand_total
            if stats_h[ST_ERR]:
                lane = self._dm.error_lane
                raise RuntimeError(
                    f"device model error lane {lane} is set in a "
                    "generated state: an encoding capacity was exceeded "
                    "(for actor models: raise net_slots)")
            with self._lock:
                self._state_count = base_states + succ_total
                novel = tail - self._arena_tail
                self._unique_count += novel
                self._arena_tail = tail
                self._head = head
                self._resident = occ  # device-tier occupancy (absolute)
                now = time.monotonic()
                self.wave_log.append((now, self._state_count))
                # Unified wave event (obs schema): the device stats
                # vector is absolute, so per-dispatch deltas come from
                # the previous processed dispatch's totals.
                wave_evt = dict(
                    meta, t=now, states=self._state_count,
                    unique=self._unique_count,
                    waves=int(stats_h[ST_WAVES]),
                    compiled=self._take_compile(),
                    successors=succ_total - succ_prev,
                    candidates=cand_total - cand_prev, novel=novel,
                    # v15: the table-probe and local-dedup loops'
                    # rounds, and the host's own time on this dispatch
                    # (its launch and processing, less the stats wait).
                    probe_rounds=int(stats_h[ST_PROBE_ROUNDS]),
                    dedup_rounds=int(stats_h[ST_DEDUP_ROUNDS]),
                    # v17: the rows the probe loop's rounds carried,
                    # each round over a chunk of the wave's B*F rows.
                    probe_slots=(int(stats_h[ST_PROBE_ROUNDS])
                                 * probe_chunk(meta["bucket"] * self._F)),
                    host_s=launch_s + (now - t_proc) - waited,
                    # Frontier rows this dispatch consumed (the head
                    # advance) — the kernel-occupancy numerator.
                    rows=head - head_prev,
                    out_rows=None, capacity=self._capacity,
                    load_factor=round(occ / self._capacity, 4),
                    overflow=False,
                    # Bandwidth gauges (obs schema v2): the resident
                    # arena footprint (packed vec rows + fps + parent
                    # fps + ebits) and the table bytes.
                    bytes_per_state=4 * self._Wrow,
                    arena_bytes=ucap * (4 * self._Wrow + 8 + 8 + 4),
                    table_bytes=self._capacity * 8,
                    # v10: wave-loop host-I/O stall since the last
                    # wave event (safe-point joins + inline writes).
                    io_stall_s=self._take_io_stall())
                if self._store.active:
                    # Tier occupancy gauges (obs schema v6): device =
                    # live arena + table; spilled arena spans ride the
                    # store's host-tier gauges.
                    wave_evt.update(
                        self._store.gauges(),
                        tier_device_rows=occ,
                        tier_device_bytes=ucap * self._arena_row_bytes()
                        + self._capacity * 8)
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
                if P:
                    disc_h = stats_h[ST_DISC:ST_DISC + P].view(np.uint64)
                    for i, prop in enumerate(properties):
                        fp = int(disc_h[i])
                        if (fp != int(SENTINEL)
                                and prop.name not in self._discoveries):
                            self._discoveries[prop.name] = fp
            if self._tracer.enabled:
                self._tracer.wave(wave_evt)
            if self._wave_obs.enabled:
                self._wave_obs.wave(wave_evt, self._tracer, self._flight)
            self._service_sync(tail)

        while True:
            if self._preempt_evt.is_set():
                # Preemption (job service): break to the normal exit —
                # the epilogue below retires every in-flight dispatch
                # and syncs the parent log, so the end-of-run
                # checkpoint is a valid resume image (same path a
                # target_state_count stop takes mid-frontier).
                self.preempted = True
                break
            with self._lock:
                # Vacuously true with zero properties — the run
                # retires immediately, like the host engines
                # (bfs.rs:117).
                done = (len(self._discoveries) == P
                        or (self._target_state_count is not None
                            and self._state_count
                            >= self._target_state_count))
            if done or (head >= tail and not inflight):
                break

            # Intended next bucket + its per-wave append bound.
            bucket = pick_bucket(self._buckets, tail - head)
            S_b = bucket * F
            growth = (occ + S_b > self._capacity // 2
                      or tail + S_b > ucap)
            ckpt_due = (self._ckpt_path is not None
                        and (self._unique_count - last_ckpt_states
                             >= self._ckpt_every * self._B))
            if (growth or ckpt_due or head >= tail) and inflight:
                # Host-side actions need processed stats at rest;
                # retire the oldest in-flight dispatch first (it may
                # already have resolved the condition).
                process(inflight.popleft())
                continue
            if growth:
                with self._tracer.span("fused.grow"):
                    # Growth at rest, before the table/arena can fill.
                    # The jitted programs chain on the device queue; the
                    # old buffers are donated + released (_releasing). An
                    # allocation failure (real or the injected grow_oom
                    # fault) sheds the top batch bucket instead of killing
                    # the run — the loop top re-derives the bucket and the
                    # headroom requirement from the shrunken ladder, so a
                    # narrower dispatch may no longer need the growth at
                    # all (OOM graceful degradation).
                    try:
                        self._grow_requested = (
                            self._capacity * 2 if occ + S_b
                            > self._capacity // 2 else self._capacity)
                        if self._faults.active:
                            self._faults.crash("grow_oom", self._tracer)
                        while occ + S_b > self._capacity // 2:
                            new_cap = self._capacity * 2
                            if self._tracer.enabled:
                                self._tracer.event(
                                    "grow", kind="table",
                                    old=self._capacity, new=new_cap)
                            visited = self._rehash_fn(self._capacity,
                                                      new_cap)(visited)
                            self._capacity = new_cap
                            self._visited = visited
                        while tail + S_b > ucap:
                            budget = self._store.device_budget \
                                if self._store.active else None
                            over = (budget is not None
                                    and 2 * ucap * self._arena_row_bytes()
                                    + self._capacity * 8 > budget)
                            if over and head > 0:
                                # Arena-span spill (tiered store): the
                                # expanded prefix [0, head) is only ever
                                # read by the parent-log sync, so sync it
                                # to the host and shift the live window
                                # down — headroom without growing past the
                                # device budget. Bit-identical: the wave
                                # reads the same [head, tail) rows in the
                                # same order, just at a new base.
                                self._fetch_parents(head)
                                shift = head
                                sh = jnp.int64(shift)
                                vecs_a = self._roll_fn(
                                    ucap, jnp.uint32, W)(vecs_a, sh)
                                fps_a = self._roll_fn(
                                    ucap, jnp.uint64)(fps_a, sh)
                                par_a = self._roll_fn(
                                    ucap, jnp.uint64)(par_a, sh)
                                eb_a = self._roll_fn(
                                    ucap, jnp.uint32)(eb_a, sh)
                                self._arena = (vecs_a, fps_a, par_a, eb_a)
                                head, tail = 0, tail - shift
                                with self._lock:
                                    self._head, self._arena_tail = head, tail
                                    self._synced_rows -= shift
                                self._store.note_arena_span(
                                    shift, shift * self._arena_row_bytes())
                                # The chained stats carry the OLD window;
                                # rebuild them at rest (discovery slots are
                                # outputs only — the dispatch takes disc
                                # separately).
                                st = np.zeros(L, np.int64)
                                st[ST_HEAD], st[ST_TAIL] = head, tail
                                st[ST_OCC], st[ST_SUCC] = occ, succ_total
                                st[ST_CAND] = cand_seen
                                st[ST_TARGET] = target_eff
                                stats_dev = jnp.asarray(st)
                                continue
                            if over and self._store.active:
                                # Nothing left to shift: the device tier
                                # must exceed its budget — recorded, not
                                # fatal.
                                self._store.note_device_pressure(
                                    2 * ucap * self._arena_row_bytes()
                                    + self._capacity * 8, budget)
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
                    ("dispatch", bucket, self._capacity, ucap, self._K))
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
        # Retire every launched dispatch (normal exit): their table
        # insertions are real, so dropping their outputs would tear the
        # frontier (states visited but their subtrees never queued). On
        # an error exit the frontier is torn by definition and
        # checkpoint() already refuses (see checkpoint()).
        while inflight:
            process(inflight.popleft())

        self._arena_tail = tail
        self._head = head
        self._fetch_parents(tail)

    # -- Parent log sync ---------------------------------------------------

    def _run(self) -> None:
        try:
            super()._run()
        finally:
            # Wake any _parent_map waiter even if the worker died before
            # its final parent fetch.
            with self._sync_cond:
                self._sync_cond.notify_all()

    def _fetch_parents(self, tail: int) -> None:
        """Appends arena rows [synced, tail) to the parent log (worker
        thread or post-join only). Always bumps the sync generation —
        a waiter must wake even when there was nothing new to fetch."""
        lo = self._synced_rows
        if tail > lo:
            with self._tracer.span("fused.parent_sync"):
                _, fps_a, par_a, _ = self._arena
                child = self._fetch_rows(fps_a, lo, tail - lo)
                parent = self._fetch_rows(par_a, lo, tail - lo)
            with self._lock:
                self._parent_log.append((child, parent))
                self._synced_rows = tail
        with self._sync_cond:
            self._sync_generation += 1
            self._sync_cond.notify_all()

    def _service_sync(self, tail: int) -> None:
        with self._sync_cond:
            wanted = self._sync_requested
            self._sync_requested = False
        if wanted:
            self._fetch_parents(tail)

    def _parent_map(self):
        if (not self._done.is_set()
                and threading.current_thread() is not self._thread):
            # Ask the worker for a parent sync at its next safe point.
            with self._sync_cond:
                self._sync_requested = True
                gen = self._sync_generation
                # A single fused dispatch can exceed any fixed timeout on a
                # slow accelerator; falling through early would
                # reconstruct paths from a stale parent log. Re-wait while
                # the worker is alive until the sync generation advances,
                # warning each minute so a wedged device is diagnosable.
                waited = 0.0
                while not self._sync_cond.wait_for(
                        lambda: (self._sync_generation != gen
                                 or self._done.is_set()), timeout=60.0):
                    if not self._thread.is_alive():
                        break
                    waited += 60.0
                    warnings.warn(
                        f"parent-log sync pending for {waited:.0f}s; the "
                        "fused dispatch is still running (slow or wedged "
                        "accelerator) — still waiting", RuntimeWarning)
        if self._error is not None:
            # The worker died mid-dispatch: rows since the last sync are
            # missing from the parent log, and reconstructing from it
            # would raise a misleading NondeterminismError. Surface the
            # real failure instead.
            raise self._error
        return super()._parent_map()

    def _reset_engine_state(self) -> None:
        """restart_from support: drop the failed run's device arena and
        sync bookkeeping (the restarted worker rebuilds both from the
        reloaded pending blocks)."""
        for attr in ("_arena", "_arena_tail", "_head"):
            self.__dict__.pop(attr, None)
        self._slice_cache.clear()
        self._synced_rows = 0
        with self._sync_cond:
            self._sync_requested = False

    # -- What the check admitted -------------------------------------------

    def arena_rows(self) -> list:
        """The states a finished or preempted check admitted, as its
        device arena holds them: one dict per shard (one on a single
        chip) with ``lanes`` (uint32 ``[rows, W]``, the model's state
        lanes, unpacked), ``fps`` and ``parents`` (the rows' and their
        parents' fingerprints; an initial state's parent is
        ``SENTINEL``), and ``head``: rows ``[0, head)`` were expanded.
        Rows that an arena-span spill moved off the device are not in
        it. Call after ``join()``."""
        if not self._done.is_set():
            raise RuntimeError("arena_rows() reads a finished check; "
                               "join() it first")
        if not hasattr(self, "_arena"):
            return []
        vecs_a, fps_a, par_a, _ = self._arena
        return [{"lanes": self._unpack_np(
                    self._fetch_rows(vecs_a, base, tail, self._Wrow)),
                 "fps": self._fetch_rows(fps_a, base, tail),
                 "parents": self._fetch_rows(par_a, base, tail),
                 "head": head}
                for base, head, tail in self._arena_spans()]

    def _arena_spans(self) -> list:
        """``(first row, head, tail)`` of each shard's arena slice."""
        return [(0, self._head, self._arena_tail)]

    # -- Checkpoint hooks --------------------------------------------------

    def _pending_blocks(self) -> list:
        head = getattr(self, "_head", 0)
        tail = getattr(self, "_arena_tail", 0)
        if not hasattr(self, "_arena") or tail <= head:
            return list(self._pending)
        vecs_a, fps_a, _, eb_a = self._arena
        return [(self._fetch_rows(vecs_a, head, tail - head, self._Wrow),
                 self._fetch_rows(fps_a, head, tail - head),
                 self._fetch_rows(eb_a, head, tail - head))]

    def _write_checkpoint(self, path: str) -> None:
        # Snapshot needs the parent log and the frontier; both live on
        # device between dispatches.
        tail = getattr(self, "_arena_tail", 0)
        if hasattr(self, "_arena"):
            self._fetch_parents(tail)
        super()._write_checkpoint(path)
