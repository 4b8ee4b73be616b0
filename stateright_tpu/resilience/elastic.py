"""Elastic multi-worker sharding: coordinator/worker BFS with shard
migration and mid-run rebalance.

Round 10 made single-*process* failures a tested code path (seeded
faults + supervised checkpoint resume); the sharded engines, though,
still ran only on a single-process virtual mesh — lose the process and
the whole run restarts from one monolithic snapshot. This module is
ROADMAP item 4's production story for preemptible fleets: the
owner-partitioned wave (the shared-hash-table design of
arXiv:1004.2772, scaled the way GPUexplore's multi-GPU study
arXiv:1801.05857 scales it) across **N workers** — OS processes over
local sockets, or in-process threads over the same sockets for the
fast test tier — where

- **membership** is heartbeat leases (:class:`~.membership.Membership`):
  a missed lease emits a ``worker_lost`` obs event and triggers shard
  *migration*, not an abort;
- **ownership** is a fixed logical partition function (``fp %
  n_partitions``) under an epoch-versioned rendezvous
  :class:`~.membership.OwnerMap` — results never depend on which
  worker hosts a partition, and every remap bumps the epoch at an
  exchange-drained barrier so in-flight rows always route by exactly
  one map;
- **durability** is per-shard checkpoint generations (format v4): each
  partition snapshots to its own :func:`~..checkpoint_format.shard_path`
  file (CRC'd, keep-last-2 PER SHARD) at a coordinator round barrier,
  plus a manifest carrying the run-global counters — so a dead
  worker's partitions are rebuilt *independently* on survivors from
  their newest valid generations;
- **elasticity** is mid-run join: a new worker registers, wins its
  rendezvous share of partitions, receives them via fresh per-shard
  snapshots at a drained barrier (no rollback, no lost work), logged
  as a ``rebalance`` event.

The wave itself reuses the engines' building blocks
(``expand_frontier`` / ``fingerprint_successors`` /
``first_occurrence_candidates``, jitted per worker) and the
checkpoint-format machinery (``make_header`` / ``write_atomic`` /
``pending_rows``) — the same packed-row path ``restart_from`` resumes
through — so a completed elastic run is **bit-identical in totals**
(state count, unique count, discovery set, final checkpoint payload)
to a single-process sharded run of the same model:
``tests/test_elastic.py`` pins kill-one-worker and join-one-worker
runs against the unfaulted single-process reference.

Transport is a deliberately simple coordinator-star over localhost TCP
with length-prefixed pickle frames (trusted same-host peers only — the
multi-host deployment swaps this layer for jax.distributed /
collectives while keeping the membership, epoch, and per-shard
generation machinery, which is the part that is actually new). The
coordinator drives synchronous rounds:

1. ``wave``: every worker expands up to ``batch_rows`` rows from its
   partitions' queues, evaluates properties, fingerprints successors,
   and returns locally-deduped outbound rows grouped by destination
   partition (sender-side dedup — the novelty-routed exchange);
2. ``deliver``: the coordinator routes each partition's rows to its
   CURRENT owner (this is the epoch-aware hop), which dedups them
   against that partition's visited set and enqueues the novel rows;
3. counters/discoveries merge; at the checkpoint cadence every worker
   snapshots every owned partition and the coordinator writes the
   manifest — one consistent generation, because the barrier has
   drained all exchange.

A loss rolls every survivor back to the newest complete generation
(counters included, so recovered totals cannot double-count), adopts
the dead worker's partitions onto the rendezvous winners, and bumps
the epoch (``migrate_done``). A join hands off at a live barrier with
no rollback (``rebalance``).

Observability (round 12, schema v5): every worker owns a
``RelayTracer`` emitting its wave/lifecycle events WHERE the work
happens, shipped in bounded batches piggybacked on round replies and
merged by the coordinator's ``TraceCollector`` into one causally
ordered trace — plus per-round straggler attribution (compute /
exchange / barrier-wait per worker, from self-reported durations) and
an always-on flight-recorder ring in every worker and the coordinator
that dumps a postmortem on crashes and ``worker_lost``. See
``obs/collect.py`` / ``obs/flight.py`` and the Observability section
of ARCHITECTURE.md.
"""

from __future__ import annotations

import os
import pickle
import queue
import socket
import struct
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional

import numpy as np

from ..obs.collect import RelayTracer, TraceCollector
from ..obs.flight import recorder_from_env
from ..obs.hist import wave_obs_from_env
from ..obs.prof import prof_from_env
from ..obs.tracer import tracer_from_env
from .faults import fault_plan_from_env
from .membership import Membership, OwnerMap

__all__ = ["ElasticChecker", "elastic_check"]


# -- Framing ---------------------------------------------------------------
#
# Length-prefixed pickle over a localhost socket. Pickle because the
# payloads are numpy blocks between trusted same-host peers the
# coordinator itself spawned; a multi-host deployment replaces this
# transport wholesale (see module docstring), not incrementally.

_LEN = struct.Struct(">Q")


def _send_msg(sock: socket.socket, obj, lock: Optional[threading.Lock]
              = None) -> None:
    data = pickle.dumps(obj, protocol=4)
    frame = _LEN.pack(len(data)) + data
    if lock is not None:
        with lock:
            sock.sendall(frame)
    else:
        sock.sendall(frame)


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    buf = bytearray()
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise ConnectionError("peer closed the socket")
        buf.extend(chunk)
    return bytes(buf)


def _recv_msg(sock: socket.socket):
    (n,) = _LEN.unpack(_recv_exact(sock, _LEN.size))
    return pickle.loads(_recv_exact(sock, n))


class _WorkerLost(Exception):
    """A worker's socket died or its lease lapsed mid-operation."""

    def __init__(self, names):
        super().__init__(f"worker(s) lost: {sorted(names)}")
        self.names = sorted(names)


class _Abort(Exception):
    """The run cannot continue (no survivors / no recoverable
    generation); surfaces as the terminal ``abort`` obs event."""


# -- Worker side -----------------------------------------------------------

class _Partition:
    """One logical shard's state on its current owner: the visited set
    (dedup fingerprints) and the pending frontier as (vecs, path-fps,
    ebits) blocks — the same block shape the engines queue."""

    __slots__ = ("visited", "queue")

    def __init__(self, visited=None, blocks=None):
        self.visited = set() if visited is None else visited
        self.queue: deque = deque(blocks or [])

    def queued_rows(self) -> int:
        return sum(len(b[1]) for b in self.queue)


class _WorkerRuntime:
    """The worker half: owns a set of partitions, expands their
    frontiers with the jitted engine building blocks, and serves the
    coordinator's command protocol over one socket."""

    def __init__(self, name: str, model_factory: Callable, cfg: dict):
        self.name = name
        #: attached by the entry functions AFTER construction: the
        #: heavy build (model, device model, jit wrapper, a process's
        #: jax import) happens before the coordinator ever sees the
        #: register, so the lease clock starts on a ready worker.
        self.sock: Optional[socket.socket] = None
        self.send_lock = threading.Lock()
        self.cfg = cfg
        self.n_parts = int(cfg["n_partitions"])
        self.B = int(cfg["batch_rows"])
        self.use_sym = bool(cfg.get("symmetry", False))
        self.parts: Dict[int, _Partition] = {}
        self._stop_hb = threading.Event()
        self._faults = fault_plan_from_env()
        #: the ownership epoch / coordinated round the LAST command ran
        #: under — stamped onto every relayed event for the collector's
        #: (epoch, round, worker, seq) merge order.
        self._epoch = 0
        self._round = 0
        #: cumulative per-worker wave totals: successors this worker
        #: generated, novel rows it accepted since its last wave event.
        self._states_total = 0
        self._novel_accum = 0
        self._compiled_once = False
        #: always-on flight recorder: the worker's last events survive
        #: its death as a postmortem dump (named for the worker, so a
        #: drill can find the casualty's file deterministically).
        self._flight = recorder_from_env(name)
        #: per-worker trace stream (obs schema v5): wave/fault events
        #: are emitted HERE, where the work happens, stamped with
        #: (worker, seq, epoch, round), and shipped to the coordinator
        #: in bounded batches piggybacked on round replies. With the
        #: coordinator untraced (``relay_trace`` off) nothing is
        #: buffered or shipped, but the stamped events still tee into
        #: the flight ring — postmortems work for dark runs too.
        self._relay = RelayTracer(
            name, buffering=bool(cfg.get("relay_trace")),
            mirror=(self._flight.record if self._flight.armed else None),
            meta={"transport": cfg.get("transport"),
                  "n_partitions": self.n_parts})
        #: the store's event sink (read lazily via owner._tracer): the
        #: worker's spill/pressure events relay with its wave stream.
        self._tracer = self._relay
        #: service observability (obs/hist.py): per-worker wave
        #: latency histograms; snapshots ride the relay (stamped
        #: worker/seq) so they merge causally at the coordinator.
        self._wave_obs = wave_obs_from_env(name)
        if self._wave_obs.enabled and self._flight.armed:
            self._flight.set_hist_source(
                self._wave_obs.final_snapshot_event)
        #: continuous wave profiler (obs/prof.py): the worker's expand
        #: is a lazy ``jax.jit`` (no AOT cost analysis), so its record
        #: carries null flops/bytes — but the sampled stage timings and
        #: ``cost_ratio`` still ride the relay as ``profile_snapshot``
        #: events (stamped worker/seq) and merge causally at the
        #: coordinator, like the r18 histogram snapshots.
        self._prof = prof_from_env(name)

        from ..model import Expectation

        model = model_factory()
        self.model = model
        self.dm = model.device_model()
        self.W = self.dm.state_width
        self.F = self.dm.max_fanout
        self.properties = model.properties()
        device_props = self.dm.device_properties()
        self.prop_fns = [device_props.get(p.name) for p in self.properties]
        self.eventually_idx = [
            i for i, p in enumerate(self.properties)
            if p.expectation is Expectation.EVENTUALLY]
        for i in self.eventually_idx:
            if self.prop_fns[i] is None:
                raise NotImplementedError(
                    "the elastic runtime requires a device predicate "
                    f"for eventually property "
                    f"{self.properties[i].name!r} (per-row bits are "
                    "cleared before the exchange, like the sharded "
                    "engines)")
        self._expand = self._build_expand()
        #: the worker's single program key; the capture records null
        #: flops/bytes (lazy jit — no AOT cost analysis) but still
        #: attributes the key so its sampled snapshots join the table.
        self._prof_pkey = f"{name}|expand|({self.B},)"
        if self._prof.enabled:
            self._prof.capture(self._prof_pkey, self._expand)
        # Tiered state store (stateright_tpu.store): partition-keyed,
        # so a partition's spilled visited rows checkpoint/migrate/drop
        # with the partition. Armed by the STpu_TIER_* env knobs (the
        # coordinator's environment reaches process workers through
        # spawn); disarmed = NULL_STORE, one attribute check per
        # deliver.
        from ..store.tiered import store_from_config

        self._store = store_from_config(
            owner=self, prefix=f"{name}-",
            n_partitions=self.n_parts,
            meta={"model_name": type(model).__name__,
                  "state_width": self.W,
                  "use_symmetry": self.use_sym})
        # Round 17: background writer for shard checkpoints and cold
        # spills. Env-knob only (STpu_ASYNC_IO) — process workers
        # inherit the coordinator's environment through spawn, so the
        # knob reaches every worker without protocol changes. The
        # checkpoint command JOINS before replying ok: the coordinator
        # writes the manifest only after every worker acked, so the
        # manifest-last crash-consistency invariant is preserved.
        from ..io.async_io import writer_from_config

        self._aio = writer_from_config(None, name=f"stpu-aio-{name}")
        self._store.attach_async(self._aio)

    # -- The jitted sender side (one compile per worker) ------------------

    def _build_expand(self):
        import jax
        import jax.numpy as jnp

        from ..tpu.engine import (eval_properties, expand_frontier,
                                  fingerprint_successors,
                                  first_occurrence_candidates)

        dm = self.dm
        prop_fns = list(self.prop_fns)
        use_sym = self.use_sym
        eventually_device = list(self.eventually_idx)

        def expand(vecs, valid, ebits):
            conds = eval_properties(prop_fns, vecs)
            succ_flat, sflat, succ_count, terminal = expand_frontier(
                dm, vecs, valid)
            dedup_fps, path_fps, _ = fingerprint_successors(
                dm, succ_flat, sflat, use_sym)
            cleared = ebits
            for i in eventually_device:
                cleared = cleared & ~jnp.where(
                    conds[i], jnp.uint32(1 << i), jnp.uint32(0))
            child_ebits = jnp.repeat(cleared, dm.max_fanout)
            # Sender-side local dedup (exchange_novel_only): only the
            # first occurrence of each distinct fingerprint rides to
            # its owner — same rule and bit-identity argument as the
            # sharded engines' novelty-routed exchange.
            send_mask = first_occurrence_candidates(dedup_fps)
            conds_out = [c for c in conds if c is not None]
            return (conds_out, succ_count, terminal, cleared, succ_flat,
                    dedup_fps, path_fps, child_ebits, send_mask)

        return jax.jit(expand)

    # -- Partition state --------------------------------------------------

    def _install_seed(self, p: int, seed) -> None:
        vecs, fps, ebits, visited = seed
        blocks = [(np.asarray(vecs, np.uint32), np.asarray(fps, np.uint64),
                   np.asarray(ebits, np.uint32))] if len(fps) else []
        if self._store.active:
            # Fresh ownership: any spilled tiers from a previous
            # assignment of this partition are stale.
            self._store.drop_partition(p)
        self.parts[p] = _Partition(
            visited=set(int(f) for f in np.asarray(visited, np.uint64)),
            blocks=blocks)

    def _visited_rows_in_ram(self) -> int:
        return sum(len(part.visited) for part in self.parts.values())

    def _maybe_spill_visited(self) -> None:
        """Host-tier budget for the in-RAM visited sets: move the
        largest partitions' sets into the store (warm, then cold under
        pressure) until the worker fits. Membership stays exact — the
        deliver path probes the store before the set."""
        budget = self._store.host_budget
        if budget is None:
            return
        while 8 * self._visited_rows_in_ram() > budget:
            p, part = max(self.parts.items(),
                          key=lambda kv: len(kv[1].visited))
            if not part.visited:
                break
            fps = np.fromiter(part.visited, np.uint64,
                              len(part.visited))
            self._store.spill_partition_rows(p, fps)
            part.visited.clear()

    def _load_partition(self, p: int, path: str,
                        want_round: Optional[int]) -> None:
        """Rebuilds partition ``p`` from its newest per-shard
        generation whose recorded round matches the target generation
        — migration and rollback both land here, through the same
        checkpoint-format machinery ``restart_from`` resumes with."""
        from ..checkpoint_format import (PREV_SUFFIX, load_checkpoint,
                                         pending_rows, shard_path,
                                         validate_header)

        base = shard_path(path, p)
        last_err: Optional[str] = None
        for candidate in (base, base + PREV_SUFFIX):
            if not os.path.exists(candidate):
                continue
            try:
                with load_checkpoint(candidate) as data:
                    header = validate_header(
                        data, model_name=type(self.model).__name__,
                        state_width=self.W, use_symmetry=self.use_sym,
                        expect_shard=(p, self.n_parts))
                    shard_hdr = header.get("shard") or {}
                    if (want_round is not None and "shard" in header
                            and int(shard_hdr.get("round", -1))
                            != int(want_round)):
                        last_err = (
                            f"{candidate}: generation round "
                            f"{shard_hdr.get('round')} != manifest "
                            f"round {want_round}")
                        continue
                    vecs = pending_rows(data, header, self.W)
                    fps = np.asarray(data["pending_fps"], np.uint64)
                    ebits = np.asarray(data["pending_ebits"], np.uint32)
                    visited = set(
                        int(f) for f in np.asarray(data["visited"],
                                                   np.uint64))
            except ValueError as e:
                last_err = str(e)
                continue
            blocks = [(vecs, fps, ebits)] if len(fps) else []
            if self._store.active:
                # The shard file is self-contained (spilled rows were
                # materialized at write); stale tiers must not shadow
                # the rebuilt set.
                self._store.drop_partition(p)
            self.parts[p] = _Partition(visited=visited, blocks=blocks)
            return
        raise ValueError(
            f"partition {p}: no valid generation at {base!r}"
            + (f" ({last_err})" if last_err else ""))

    def _write_partition(self, p: int, path: str, round_: int,
                         epoch: int) -> None:
        from ..checkpoint_format import (make_header, shard_path,
                                         write_atomic)

        part = self.parts[p]
        visited = np.fromiter(sorted(part.visited), np.uint64,
                              len(part.visited))
        if self._store.active:
            # Spilled rows materialize into the shard file: a per-shard
            # generation must stay self-contained so migration can
            # rebuild the partition anywhere (honesty note: elastic
            # shard snapshots do NOT use v5 cold refs — the segment
            # files live on the casualty's disk).
            spilled = self._store.partition_fps(p)
            if len(spilled):
                visited = np.union1d(visited, spilled)
        blocks = list(part.queue)
        if blocks:
            vecs = np.concatenate([b[0] for b in blocks])
            fps = np.concatenate([b[1] for b in blocks])
            ebits = np.concatenate([b[2] for b in blocks])
        else:
            vecs = np.zeros((0, self.W), np.uint32)
            fps = np.zeros(0, np.uint64)
            ebits = np.zeros(0, np.uint32)
        header = make_header(
            model_name=type(self.model).__name__, state_width=self.W,
            state_count=len(visited),
            unique_count=len(visited),
            use_symmetry=self.use_sym, discoveries={},
            shard={"index": p, "of": self.n_parts, "round": round_,
                   "epoch": epoch})
        payload = dict(
            header=header, visited=visited, pending_vecs=vecs,
            pending_fps=fps, pending_ebits=ebits)
        # Payload assembly stays on the command thread (the snapshot is
        # captured at the rest point); only the CRC/serialize/rename
        # rides the writer. Under async the next partition's payload
        # builds while this one writes; the handler joins before the
        # ok reply so the coordinator's manifest stays last.
        self._aio.submit(
            lambda: write_atomic(shard_path(path, p), payload),
            kind="shard")

    # -- Command handlers -------------------------------------------------

    def _take_batch(self, rows: int):
        """Up to ``rows`` frontier rows across owned partitions, in
        partition order (the engines' block-splitting discipline)."""
        parts_vecs, parts_fps, parts_ebits = [], [], []
        taken = 0
        for p in sorted(self.parts):
            q = self.parts[p].queue
            while q and taken < rows:
                vecs, fps, ebits = q[0]
                k = len(fps)
                take = min(k, rows - taken)
                if take == k:
                    q.popleft()
                    parts_vecs.append(vecs)
                    parts_fps.append(fps)
                    parts_ebits.append(ebits)
                else:
                    parts_vecs.append(vecs[:take])
                    parts_fps.append(fps[:take])
                    parts_ebits.append(ebits[:take])
                    q[0] = (vecs[take:], fps[take:], ebits[take:])
                taken += take
            if taken >= rows:
                break
        return parts_vecs, parts_fps, parts_ebits, taken

    def _queued(self) -> Dict[int, int]:
        return {p: part.queued_rows() for p, part in self.parts.items()}

    def _host_conds(self, conds_out, batch_vecs, n):
        """Reattaches device conds to property slots; host-fallback
        slots decode each valid batch row once (the engines'
        ``_eval_host_conds`` discipline)."""
        conds: List[np.ndarray] = []
        it = iter(conds_out)
        decoded = None
        for i, fn in enumerate(self.prop_fns):
            if fn is not None:
                conds.append(np.asarray(next(it)))
                continue
            if decoded is None:
                decode = self.dm.decode
                decoded = [(r, decode(batch_vecs[r])) for r in range(n)]
            cond = np.zeros(len(batch_vecs), bool)
            prop_cond = self.properties[i].condition
            for r, state in decoded:
                cond[r] = bool(prop_cond(self.model, state))
            conds.append(cond)
        return conds

    def _handle_wave(self, cmd: dict) -> dict:
        from ..model import Expectation

        t_start = time.monotonic()
        self._round = int(cmd.get("round", self._round))
        self._epoch = int(cmd.get("epoch", self._epoch))
        self._faults.crash("worker_crash", wave=self._round,
                           worker=self.name)
        B = self.B
        parts_vecs, parts_fps, parts_ebits, n = self._take_batch(B)
        if n == 0:
            # Still a barrier participant: compute_s rides back so the
            # straggler attribution sees an (idle) segment, but an
            # empty wave emits no event — nothing happened here.
            return {"ok": True, "successors": 0, "candidates": 0,
                    "hits": {}, "out": {}, "queued": self._queued(),
                    "compute_s": round(time.monotonic() - t_start, 6)}
        batch_vecs = np.zeros((B, self.W), np.uint32)
        batch_fps = np.zeros(B, np.uint64)
        batch_ebits = np.zeros(B, np.uint32)
        row = 0
        for vecs, fps, ebits in zip(parts_vecs, parts_fps, parts_ebits):
            k = len(fps)
            batch_vecs[row:row + k] = vecs
            batch_fps[row:row + k] = fps
            batch_ebits[row:row + k] = ebits
            row += k
        valid = np.arange(B) < n

        prof_s = t0 = None
        if self._prof.enabled and self._prof.should_sample(
                self._prof_pkey):
            t0 = time.monotonic()
        (conds_out, succ_count, terminal, cleared, succ_flat, dedup_fps,
         path_fps, child_ebits, send_mask) = self._expand(
            batch_vecs, valid, batch_ebits)
        terminal = np.asarray(terminal)
        cleared = np.asarray(cleared)
        succ_flat = np.asarray(succ_flat)
        dedup_fps = np.asarray(dedup_fps)
        path_fps = np.asarray(path_fps)
        child_ebits = np.asarray(child_ebits)
        send_mask = np.asarray(send_mask)
        if t0 is not None:
            # The np.asarray conversions above already materialized
            # every output — the worker's expand is synchronous, so
            # this rest point costs nothing extra (obs/prof.py).
            prof_s = time.monotonic() - t0

        conds = self._host_conds(conds_out, batch_vecs, n)

        # Discoveries on the expanded batch (first hit per property, in
        # batch order — the engines' rule).
        hits: Dict[str, int] = {}
        for i, prop in enumerate(self.properties):
            if prop.expectation is Expectation.ALWAYS:
                hit = valid & ~conds[i]
            elif prop.expectation is Expectation.SOMETIMES:
                hit = valid & conds[i]
            else:
                continue
            rows = np.flatnonzero(hit)
            if rows.size:
                hits.setdefault(prop.name, int(batch_fps[rows[0]]))
        if self.eventually_idx:
            for r in np.flatnonzero(terminal[:n] & (cleared[:n] != 0)):
                for i in self.eventually_idx:
                    prop = self.properties[i]
                    if (int(cleared[r]) >> i) & 1 \
                            and prop.name not in hits:
                        hits[prop.name] = int(batch_fps[r])

        # Outbound rows grouped by destination partition.
        idx = np.flatnonzero(send_mask)
        out: Dict[int, tuple] = {}
        if idx.size:
            dest = (dedup_fps[idx] % np.uint64(self.n_parts)).astype(
                np.int64)
            for p in np.unique(dest):
                rows = idx[dest == p]
                out[int(p)] = (succ_flat[rows], dedup_fps[rows],
                               path_fps[rows], child_ebits[rows])
        successors = int(np.asarray(succ_count))
        self._states_total += successors
        compiled, self._compiled_once = (not self._compiled_once,
                                         True)
        # The per-worker wave event (schema v5), emitted where the
        # work happened: cumulative counts are THIS worker's (they
        # rewind only across a relay rotation, which starts a new run),
        # novel is what this worker's partitions accepted since its
        # last wave event (owner-side dedup happens in deliver).
        novel, self._novel_accum = self._novel_accum, 0
        from ..checker.base import host_store_capacity

        in_ram = self._visited_rows_in_ram()
        capacity = host_store_capacity(in_ram)
        evt = {
            "t": round(time.monotonic(), 6),
            "states": self._states_total,
            "unique": in_ram + (self._store.spilled_rows
                                if self._store.active else 0),
            "bucket": B, "waves": 1, "inflight": 0,
            "compiled": compiled, "successors": successors,
            "candidates": int(idx.size), "novel": novel,
            # Real host-store occupancy gauges (schema v6; these
            # shipped as permanent nulls through v5).
            "out_rows": novel, "capacity": capacity,
            "load_factor": round(in_ram / capacity, 4),
            "overflow": False, "bytes_per_state": 4 * self.W,
            "arena_bytes": None, "table_bytes": 8 * in_ram,
            "epoch": self._epoch, "round": self._round,
            "tier_host_rows": in_ram, "tier_host_bytes": 8 * in_ram}
        if self._store.active:
            g = self._store.gauges()
            evt["tier_host_rows"] += g["tier_host_rows"]
            evt["tier_host_bytes"] += g["tier_host_bytes"]
            evt["tier_disk_rows"] = g["tier_disk_rows"]
            evt["tier_disk_bytes"] = g["tier_disk_bytes"]
        if self._prof.enabled:
            # v13 cost stamping + (on sampled expands) the
            # profile_snapshot roofline event — it rides the relay
            # with the wave stream, stamped worker/seq.
            self._prof.wave(evt, self._prof_pkey, prof_s, self._relay,
                            self._flight)
        self._relay.wave(evt)
        if self._wave_obs.enabled:
            self._wave_obs.wave(evt, self._relay, self._flight)
        return {"ok": True, "successors": successors,
                "candidates": int(idx.size), "hits": hits, "out": out,
                "queued": self._queued(),
                "compute_s": round(time.monotonic() - t_start, 6),
                # Compact per-worker tier summary (None when the store
                # is disarmed) — the coordinator's store aggregate.
                "store": ({"spilled_rows": int(self._store.spilled_rows),
                           "disk_rows": int(self._store.cold_rows),
                           "host_rows": int(self._store.warm_rows)}
                          if self._store.active else None)}

    def _handle_deliver(self, cmd: dict) -> dict:
        t_start = time.monotonic()
        novel_total = 0
        err_lane = self.dm.error_lane
        for p in sorted(cmd["blocks"]):
            part = self.parts.get(p)
            if part is None:
                return {"ok": False,
                        "error": f"delivery for partition {p} this "
                                 f"worker does not own (epoch skew)"}
            blocks = cmd["blocks"][p]
            vecs = np.concatenate([b[0] for b in blocks])
            dfps = np.concatenate([b[1] for b in blocks])
            pfps = np.concatenate([b[2] for b in blocks])
            ebits = np.concatenate([b[3] for b in blocks])
            # First occurrence within the concatenated receive order,
            # then membership against the partition's visited set — the
            # owner-side dedup of the sharded exchange.
            _, first_idx = np.unique(dfps, return_index=True)
            first = np.zeros(len(dfps), bool)
            first[first_idx] = True
            visited = part.visited
            rows = np.flatnonzero(first)
            if self._store.active and self._store.spilled_rows \
                    and rows.size:
                # Spilled-tier membership first: a fingerprint whose
                # set was moved warm/cold must not be re-counted (the
                # engines' per-wave host probe, partition-scoped).
                rows = rows[~self._store.probe_partition(p, dfps[rows])]
            keep = []
            for r in rows:
                fp = int(dfps[r])
                if fp not in visited:
                    visited.add(fp)
                    keep.append(r)
            if not keep:
                continue
            keep = np.asarray(keep)
            new_vecs = vecs[keep]
            if err_lane is not None and new_vecs[:, err_lane].any():
                return {"ok": False,
                        "error": f"device model error lane {err_lane} "
                                 "is set in a generated state: an "
                                 "encoding capacity was exceeded"}
            part.queue.append((new_vecs, pfps[keep], ebits[keep]))
            novel_total += len(keep)
        self._novel_accum += novel_total
        if self._store.active:
            self._maybe_spill_visited()
        return {"ok": True, "novel": novel_total,
                "queued": self._queued(),
                "exchange_s": round(time.monotonic() - t_start, 6)}

    def _handle(self, cmd: dict) -> Optional[dict]:
        op = cmd["cmd"]
        if op == "wave":
            return self._handle_wave(cmd)
        if op == "deliver":
            return self._handle_deliver(cmd)
        if op == "assign":
            if "epoch" in cmd:
                self._epoch = int(cmd["epoch"])
            if cmd.get("reset"):
                self.parts.clear()
                if self._store.active:
                    self._store.reset()
                # A reassignment rewinds/re-bases this worker's
                # cumulative counters (rollback migration, join
                # handoff), so the relayed stream starts a NEW run —
                # the lint's per-run monotonicity survives, and seq
                # keeps counting across the rotation.
                self._states_total = 0
                self._novel_accum = 0
                self._relay.rotate({"reassigned_at_epoch": self._epoch})
            for p, seed in (cmd.get("seed") or {}).items():
                self._install_seed(int(p), seed)
            for p, (path, want_round) in (cmd.get("load") or {}).items():
                self._load_partition(int(p), path, want_round)
            return {"ok": True, "queued": self._queued(),
                    "unique": {p: len(part.visited)
                               for p, part in self.parts.items()}}
        if op == "drop":
            for p in cmd["partitions"]:
                self.parts.pop(int(p), None)
                if self._store.active:
                    self._store.drop_partition(int(p))
            # Dropping partitions shrinks this worker's visited union;
            # rotate so the next wave's smaller cumulative ``unique``
            # starts a fresh run instead of going backwards in the old
            # one.
            self._relay.rotate({"dropped": len(cmd["partitions"])})
            return {"ok": True, "queued": self._queued()}
        if op == "checkpoint":
            parts = cmd.get("partitions")
            parts = sorted(self.parts) if parts is None else parts
            for p in parts:
                self._write_partition(int(p), cmd["path"],
                                      int(cmd["round"]),
                                      int(cmd["epoch"]))
            # Safe point: all shard writes must have landed before the
            # ok reply — the coordinator writes the manifest only once
            # every worker acked, so a crash mid-write leaves the old
            # generation authoritative. A writer-thread fault (torn
            # shard, disk full) surfaces here and rides the error reply.
            self._aio.join()
            return {"ok": True,
                    "unique": {p: len(self.parts[p].visited)
                               for p in parts}}
        if op == "stop":
            # Clean exit: drain the background writer (pending spills
            # land or are dropped; either is safe — warm rows stay warm
            # until a landing, and unmanifested shards are inert).
            self._aio.close()
            return None  # signals a clean exit
        return {"ok": False, "error": f"unknown command {op!r}"}

    # -- Main loop ---------------------------------------------------------

    def _heartbeat_loop(self, interval: float) -> None:
        while not self._stop_hb.wait(interval):
            try:
                _send_msg(self.sock, {"msg": "heartbeat",
                                      "worker": self.name},
                          self.send_lock)
            except OSError:
                return

    def serve(self, kill_event: Optional[threading.Event] = None) -> None:
        """Serves coordinator commands until ``stop``, death, or an
        injected crash. ``kill_event`` (thread transport) simulates a
        SIGKILL: die abruptly — no reply, no goodbye — at the next
        command, which is exactly what the coordinator's lease/EOF
        machinery must absorb."""
        from .faults import InjectedFault

        try:
            # Register FIRST, then start heartbeating: the acceptor
            # treats the first frame on a fresh socket as the hello,
            # and a heartbeat winning the send_lock race would get the
            # whole worker silently dropped.
            _send_msg(self.sock, {"msg": "register", "worker": self.name},
                      self.send_lock)
            hb = threading.Thread(
                target=self._heartbeat_loop,
                args=(float(self.cfg.get("heartbeat_s", 0.25)),),
                daemon=True)
            hb.start()
            while True:
                cmd = _recv_msg(self.sock)
                if kill_event is not None and kill_event.is_set():
                    return  # vanish without a reply (simulated SIGKILL)
                try:
                    reply = self._handle(cmd)
                except InjectedFault as e:
                    # worker_crash fired: die the hard way. The fault
                    # event is already flushed by the plan's emitter;
                    # the flight ring additionally records it and dumps
                    # — the postmortem's LAST event is the fault point,
                    # which is the whole point of a flight recorder.
                    if self._flight.armed:
                        self._flight.record_event(
                            "fault", point="worker_crash", hit=0,
                            mode="crash", worker=self.name,
                            error=str(e)[:300])
                        self._flight.dump(
                            f"injected worker_crash: {e}")
                    if self.cfg.get("transport") == "process":
                        os._exit(17)
                    return
                except Exception as e:  # noqa: BLE001 — surface upward
                    reply = {"ok": False,
                             "error": f"{type(e).__name__}: {e}"[:500]}
                stop = reply is None
                reply = {"ok": True} if stop else reply
                # Echo the command's sequence number: the coordinator
                # drops stale replies (a round torn by a loss leaves
                # unread replies in buffers) by matching on it.
                reply["seq"] = cmd.get("seq")
                # Piggyback the relayed trace batch (bounded) on the
                # reply that was going to the coordinator anyway.
                batch, dropped = self._relay.drain()
                if batch:
                    reply["trace"] = batch
                if dropped:
                    reply["trace_dropped"] = dropped
                _send_msg(self.sock, reply, self.send_lock)
                if stop:
                    return
        except (ConnectionError, OSError):
            return  # the coordinator went away; nothing to report to
        finally:
            self._stop_hb.set()
            try:
                self.sock.close()
            except OSError:
                pass


def _worker_thread_main(addr, name, model_factory, cfg, kill_event):
    runtime = None
    try:
        runtime = _WorkerRuntime(name, model_factory, cfg)
        runtime.sock = socket.create_connection(addr)
        runtime.serve(kill_event)
    except Exception as e:  # noqa: BLE001 — a dead worker is a lease lapse
        if runtime is not None and runtime._flight.armed:
            # The unhandled-exception postmortem: the coordinator only
            # sees a lease lapse; the ring's dump says what the worker
            # was doing when it died.
            runtime._flight.dump(f"{type(e).__name__}: {e}")
        if runtime is not None and runtime.sock is not None:
            try:
                runtime.sock.close()
            except OSError:
                pass


def _worker_process_entry(addr, name, model_factory, cfg):
    """Module-level so multiprocessing's spawn context can import it.
    The spawned interpreter inherits JAX_PLATFORMS from the parent
    environment (the tests pin cpu), builds its own backend, and is
    exactly the per-host process a jax.distributed deployment runs.
    Heavy construction (the jax import) runs BEFORE connecting, so
    the coordinator's lease clock starts on a ready worker."""
    runtime = _WorkerRuntime(name, model_factory, cfg)
    runtime.sock = socket.create_connection(addr)
    try:
        runtime.serve(None)
    except Exception as e:  # noqa: BLE001 — dump, then die as before
        if runtime._flight.armed:
            runtime._flight.dump(f"{type(e).__name__}: {e}")
        raise


# -- Coordinator -----------------------------------------------------------

class _Handle:
    """The coordinator's view of one worker."""

    __slots__ = ("name", "sock", "thread", "proc", "kill_event")

    def __init__(self, name, sock, thread=None, proc=None,
                 kill_event=None):
        self.name = name
        self.sock = sock
        self.thread = thread
        self.proc = proc
        self.kill_event = kill_event


def _backend() -> str:
    import jax

    return jax.default_backend()


class ElasticChecker:
    """Runs an owner-partitioned BFS over ``workers`` elastic workers.

    ``model_factory`` must be picklable for ``transport="process"``
    (e.g. ``functools.partial(TwoPhaseSys, 3)``); any callable works
    for ``transport="thread"``. The checker facade mirrors the engine
    API (``join`` / ``state_count`` / ``unique_state_count`` /
    ``discoveries`` / ``wave_log`` / ``dispatch_log``) so bench and
    tests drive it like any other engine — ``discoveries()`` returns
    ``{property name: fingerprint}`` (no Path reconstruction: the
    parent map is distributed; replay it on a single-process engine
    from the same checkpoint when a trace is needed).

    Deterministic chaos for tests/bench: ``kill_at={round: worker}``
    kills a worker just before that coordinated round;
    ``join_at={round: name}`` spawns and admits a new worker at that
    round's barrier. Both are also drivable live via
    :meth:`kill_worker` / :meth:`add_worker`.
    """

    def __init__(self, model_factory: Callable, *, workers: int = 2,
                 n_partitions: int = 8, batch_rows: int = 256,
                 checkpoint_path: Optional[str] = None,
                 checkpoint_every_rounds: int = 4,
                 transport: str = "thread",
                 lease_s: float = 15.0, heartbeat_s: float = 0.25,
                 symmetry: bool = False,
                 target_state_count: Optional[int] = None,
                 resume_from: Optional[str] = None,
                 kill_at: Optional[Dict[int, str]] = None,
                 join_at: Optional[Dict[int, str]] = None,
                 spawn_timeout_s: float = 120.0,
                 command_timeout_s: float = 300.0):
        if transport not in ("thread", "process"):
            raise ValueError(
                f"transport must be 'thread' or 'process', got "
                f"{transport!r}")
        if transport == "process" and _backend() == "tpu":
            # A chip belongs to one process: spawn children that import
            # JAX would contend with this one for it.
            raise ValueError(
                "transport='process' is refused on a TPU backend: each "
                "worker process would need the chip this process holds; "
                "use transport='thread'")
        if workers < 1:
            raise ValueError("need at least one worker")
        self._factory = model_factory
        self._n_parts = int(n_partitions)
        self._B = int(batch_rows)
        self._ckpt = checkpoint_path
        self._ckpt_every = max(1, int(checkpoint_every_rounds))
        self._transport = transport
        self._lease_s = float(lease_s)
        self._hb_s = float(heartbeat_s)
        self._target = target_state_count
        self._resume_from = resume_from
        self._kill_at = dict(kill_at or {})
        self._join_at = dict(join_at or {})
        self._spawn_timeout = float(spawn_timeout_s)
        self._cmd_timeout = float(command_timeout_s)

        self._model = model_factory()
        # a symmetric model is checked under symmetry whatever the
        # argument says, as its ``checker()`` is
        self._symmetry = bool(symmetry) or bool(
            getattr(self._model, "symmetric", False))
        self._dm = self._model.device_model()
        self._W = self._dm.state_width
        from ..model import Expectation

        self._ebits_all = 0
        self._n_properties = len(self._model.properties())
        for i, p in enumerate(self._model.properties()):
            if p.expectation is Expectation.EVENTUALLY:
                self._ebits_all |= 1 << i

        self._lock = threading.Lock()
        self._done = threading.Event()
        self._stop_req = threading.Event()
        self._error: Optional[BaseException] = None
        self._state_count = 0
        self._unique_count = 0
        self._discoveries: Dict[str, int] = {}
        self._round = 0
        self._queued: Dict[int, int] = {}
        self._migrations = 0
        self._rebalances = 0
        #: last per-worker tier summary off the wave replies (None
        #: entries never land) — the coordinator's store aggregate.
        self._worker_store: Dict[str, dict] = {}
        #: lifecycle records (worker_lost / migrate_done / rebalance /
        #: worker_join), mirroring the obs events, for tests and bench.
        self.events: List[dict] = []
        self.wave_log: List[tuple] = []
        self.dispatch_log: List[dict] = []

        self._members: Dict[str, _Handle] = {}
        #: command sequence counter: replies echo it, so a round torn
        #: by a loss cannot desync the protocol (stale replies parked
        #: in a survivor's socket buffer are matched and dropped).
        self._seq = 0
        self._membership = Membership(self._lease_s)
        self._map = OwnerMap(self._n_parts,
                             [f"w{i}" for i in range(int(workers))])
        self._next_worker = int(workers)
        self._incoming: "queue.Queue" = queue.Queue()
        self._pending_joins: List[str] = []

        self._listener = socket.create_server(("127.0.0.1", 0))
        self._addr = self._listener.getsockname()
        self._accept_stop = threading.Event()
        self._acceptor = threading.Thread(target=self._accept_loop,
                                          daemon=True)
        self._acceptor.start()

        self._tracer = tracer_from_env("elastic", meta={
            "model": type(self._model).__name__,
            "workers": list(self._map.owners),
            "n_partitions": self._n_parts,
            "batch_rows": self._B,
            "transport": transport})
        #: always-on coordinator flight ring: sees the coordinator's
        #: own round entries, lifecycle events, AND every merged
        #: worker event — so a worker_lost dump contains the
        #: casualty's last relayed waves even when the worker itself
        #: could not dump (SIGKILL leaves no exception handler).
        self._flight = recorder_from_env(
            f"elastic-coordinator-{os.getpid()}")
        #: service observability (obs/hist.py): round-summary latency
        #: histograms, SLO tracking, and slow-wave anomaly attribution
        #: over the coordinator's dispatch entries; the collector also
        #: feeds per-worker compute-vs-wait segments into it.
        self._wave_obs = wave_obs_from_env("elastic")
        if self._wave_obs.enabled and self._flight.armed:
            self._flight.set_hist_source(
                self._wave_obs.final_snapshot_event)
        #: postmortem dump paths this run produced (worker losses,
        #: terminal aborts) — surfaced via ``elastic_obs`` and bench.
        self.postmortems: List[str] = []
        #: merges the workers' relayed streams into the trace file in
        #: (epoch, round, worker, seq) order and owns the straggler
        #: attribution (obs/collect.py).
        self._collector = TraceCollector(self._tracer,
                                         flight=self._flight,
                                         obs=self._wave_obs)
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    # -- Transport plumbing ------------------------------------------------

    def _accept_loop(self) -> None:
        self._listener.settimeout(0.25)
        while not self._accept_stop.is_set():
            try:
                sock, _ = self._listener.accept()
            except socket.timeout:
                continue
            except OSError:
                return
            try:
                sock.settimeout(10.0)
                hello = _recv_msg(sock)
                sock.settimeout(None)
            except (ConnectionError, OSError, struct.error):
                sock.close()
                continue
            if hello.get("msg") == "register":
                self._incoming.put((hello["worker"], sock))
            else:
                sock.close()

    def _spawn_worker(self, name: str) -> None:
        if name in self._members:
            raise ValueError(
                f"worker name {name!r} is already a live member — a "
                "duplicate would clobber its handle and strand its "
                "partitions")
        cfg = {"n_partitions": self._n_parts, "batch_rows": self._B,
               "symmetry": self._symmetry, "heartbeat_s": self._hb_s,
               "transport": self._transport,
               # Workers buffer/ship their relayed streams only when
               # the coordinator is actually writing a trace; their
               # flight recorders stay on regardless.
               "relay_trace": self._tracer.enabled}
        if self._transport == "thread":
            kill_event = threading.Event()
            t = threading.Thread(
                target=_worker_thread_main,
                args=(self._addr, name, self._factory, cfg, kill_event),
                daemon=True)
            t.start()
            self._members[name] = _Handle(name, None, thread=t,
                                          kill_event=kill_event)
        else:
            import multiprocessing

            ctx = multiprocessing.get_context("spawn")
            proc = ctx.Process(
                target=_worker_process_entry,
                args=(self._addr, name, self._factory, cfg), daemon=True)
            proc.start()
            self._members[name] = _Handle(name, None, proc=proc)

    def _await_register(self, names, deadline: float) -> None:
        waiting = set(names)
        while waiting:
            timeout = deadline - time.monotonic()
            if timeout <= 0:
                raise _Abort(
                    f"worker(s) {sorted(waiting)} never registered "
                    f"within {self._spawn_timeout:.0f}s")
            try:
                name, sock = self._incoming.get(timeout=min(timeout, 1.0))
            except queue.Empty:
                continue
            handle = self._members.get(name)
            if handle is None:
                sock.close()
                continue
            handle.sock = sock
            self._membership.add(name)
            waiting.discard(name)

    def _reap(self, name: str) -> None:
        handle = self._members.pop(name, None)
        self._membership.drop(name)
        if handle is None:
            return
        if handle.sock is not None:
            try:
                handle.sock.close()
            except OSError:
                pass
        if handle.proc is not None and handle.proc.is_alive():
            handle.proc.kill()
            handle.proc.join(timeout=5.0)
        if handle.kill_event is not None:
            handle.kill_event.set()

    def _next_seq(self) -> int:
        self._seq += 1
        return self._seq

    def _send(self, name: str, msg: dict) -> None:
        handle = self._members[name]
        try:
            _send_msg(handle.sock, msg)
        except OSError as e:
            raise _WorkerLost([name]) from e

    def _await_reply(self, name: str, seq: Optional[int] = None) -> dict:
        """One reply from ``name``, absorbing heartbeats and dropping
        stale replies (``seq`` mismatch — leftovers of a round a loss
        tore down). Two liveness bounds, because they catch different
        deaths: the LEASE (no traffic at all — dead process, dead
        socket) and the COMMAND TIMEOUT (a worker wedged inside a
        command whose heartbeat thread is still dutifully beating —
        the preemptible-accelerator wedge mode; heartbeats prove the
        process lives, not that it progresses)."""
        handle = self._members[name]
        cmd_deadline = time.monotonic() + self._cmd_timeout
        handle.sock.settimeout(min(1.0, self._lease_s / 4))
        try:
            while True:
                try:
                    obj = _recv_msg(handle.sock)
                except socket.timeout:
                    if (self._membership.remaining(name) < 0
                            or time.monotonic() > cmd_deadline):
                        raise _WorkerLost([name]) from None
                    continue
                self._membership.beat(name)
                if obj.get("msg") == "heartbeat":
                    continue
                # Harvest the piggybacked trace batch off EVERY reply
                # — stale ones included: those events were already
                # drained from the worker's relay and exist nowhere
                # else.
                batch = obj.pop("trace", None)
                dropped = obj.pop("trace_dropped", 0)
                if batch or dropped:
                    self._collector.add_batch(name, batch or [],
                                              int(dropped))
                if seq is not None and obj.get("seq") != seq:
                    continue  # stale reply from a torn round
                return obj
        except (ConnectionError, OSError) as e:
            raise _WorkerLost([name]) from e
        finally:
            try:
                handle.sock.settimeout(None)
            except OSError:
                pass

    def _broadcast(self, msg: dict, names=None) -> Dict[str, dict]:
        """Send to every (or the named) live workers, then collect all
        replies; socket failures and lease lapses surface as
        :class:`_WorkerLost` carrying every casualty of the round."""
        names = self._membership.workers() if names is None else names
        seq = self._next_seq()
        msg = dict(msg, seq=seq)
        lost: List[str] = []
        for name in names:
            try:
                self._send(name, msg)
            except _WorkerLost as e:
                lost.extend(e.names)
        replies: Dict[str, dict] = {}
        for name in names:
            if name in lost:
                continue
            try:
                replies[name] = self._await_reply(name, seq)
            except _WorkerLost as e:
                lost.extend(e.names)
        if lost:
            raise _WorkerLost(lost)
        for name, reply in replies.items():
            if not reply.get("ok"):
                raise _Abort(
                    f"worker {name}: {reply.get('error', 'failed')}")
        return replies

    # -- Seeding / generations ---------------------------------------------

    def _seed_blocks(self):
        """Initial states, encoded/fingerprinted/deduplicated exactly
        like the engines' ``__init__`` seeding, bucketed by partition."""
        import jax.numpy as jnp

        from ..tpu.hashing import host_fp64

        model, dm = self._model, self._dm
        init_states = [s for s in model.init_states()
                       if model.within_boundary(s)]
        self._state_count = len(init_states)
        seen_reps = set()
        rows = []  # (partition, vec, raw fp, rep fp)
        for s in init_states:
            vec = np.asarray(dm.encode(s), np.uint32)
            fp = host_fp64(vec)
            if self._symmetry:
                rep = np.asarray(dm.representative(jnp.asarray(vec)),
                                 np.uint32)
                rep_fp = host_fp64(rep)
            else:
                rep_fp = fp
            if rep_fp in seen_reps:
                continue
            seen_reps.add(rep_fp)
            rows.append((int(rep_fp) % self._n_parts, vec, fp, rep_fp))
        self._unique_count = len(rows)
        seeds = {}
        for p in range(self._n_parts):
            mine = [r for r in rows if r[0] == p]
            vecs = (np.stack([r[1] for r in mine]).astype(np.uint32)
                    if mine else np.zeros((0, self._W), np.uint32))
            fps = np.array([r[2] for r in mine], np.uint64)
            ebits = np.full(len(mine), self._ebits_all, np.uint32)
            visited = np.array([r[3] for r in mine], np.uint64)
            seeds[p] = (vecs, fps, ebits, visited)
        return seeds

    def _assign_all(self, seeds=None, load_round=None,
                    load_path=None) -> None:
        """(Re)assigns every partition per the current map — seeding a
        fresh run, resuming a coordinator (``load_path`` = the resumed
        manifest's path), or rolling everyone back to a generation
        (``load_round`` at the run's own checkpoint path)."""
        load_path = self._ckpt if load_path is None else load_path
        seq = self._next_seq()
        for name in self._membership.workers():
            parts = self._map.partitions_of(name)
            msg = {"cmd": "assign", "partitions": list(parts),
                   "epoch": self._map.epoch, "reset": True, "seq": seq}
            if seeds is not None:
                msg["seed"] = {p: seeds[p] for p in parts}
            else:
                msg["load"] = {p: (load_path, load_round)
                               for p in parts}
            self._send(name, msg)
        queued: Dict[int, int] = {}
        for name in self._membership.workers():
            reply = self._await_reply(name, seq)
            if not reply.get("ok"):
                raise _Abort(f"worker {name}: assign failed: "
                             f"{reply.get('error')}")
            queued.update({int(p): r
                           for p, r in reply["queued"].items()})
        self._queued = queued

    def _write_generation(self, round_: int) -> None:
        """The full-barrier checkpoint: every worker snapshots every
        owned partition at ``round_``, then the manifest lands LAST —
        so the newest valid manifest always names a round whose shard
        files (current or ``.prev``) all exist. Exchange is drained by
        construction (we only checkpoint between rounds)."""
        if self._ckpt is None:
            return
        from ..checkpoint_format import make_header, write_atomic

        replies = self._broadcast({
            "cmd": "checkpoint", "partitions": None, "path": self._ckpt,
            "round": round_, "epoch": self._map.epoch})
        part_unique = np.zeros(self._n_parts, np.uint64)
        for reply in replies.values():
            for p, u in reply.get("unique", {}).items():
                part_unique[int(p)] = u
        header = make_header(
            model_name=type(self._model).__name__, state_width=self._W,
            state_count=self._state_count,
            unique_count=self._unique_count,
            use_symmetry=self._symmetry, discoveries=self._discoveries,
            elastic={"round": round_, "epoch": self._map.epoch,
                     "partitions": self._n_parts,
                     "workers": list(self._membership.workers())})
        write_atomic(self._ckpt, dict(header=header,
                                      partition_unique=part_unique))

    def _read_generation(self, source: Optional[str] = None) -> dict:
        """The newest valid manifest's round + run-global counters —
        what a rollback (or a resumed coordinator, via ``source`` =
        the ``resume_from`` manifest) restores."""
        from ..checkpoint_format import load_checkpoint, validate_header
        from .supervisor import newest_valid_checkpoint

        source = self._ckpt if source is None else source
        path = newest_valid_checkpoint(source)
        if path is None:
            raise _Abort(
                f"no valid checkpoint generation at {source!r} to "
                "recover from")
        with load_checkpoint(path) as data:
            header = validate_header(
                data, model_name=type(self._model).__name__,
                state_width=self._W, use_symmetry=self._symmetry)
            elastic = header.get("elastic")
            if not elastic:
                raise _Abort(
                    f"checkpoint {path!r} is not an elastic manifest "
                    "(no per-shard generation to recover)")
            return {
                "round": int(elastic["round"]),
                "state_count": int(header["state_count"]),
                "unique_count": int(header["unique_count"]),
                "discoveries": {k: int(v) for k, v
                                in header["discoveries"].items()},
            }

    # -- Membership transitions --------------------------------------------

    def _emit_lifecycle(self, etype: str, **fields) -> None:
        record = dict(fields, type=etype, t=time.monotonic())
        with self._lock:
            self.events.append(record)
        if self._flight.armed:
            self._flight.record_event(etype, **fields)
        if self._tracer.enabled:
            self._tracer.event(etype, _flush=True, **fields)

    def _recover(self, lost: List[str]) -> None:
        """Migration: roll every survivor back to the newest complete
        generation, adopt the dead workers' partitions onto the
        rendezvous winners, bump the epoch. Survivors dying mid-
        recovery just widen the casualty list and retry."""
        pending = list(lost)
        #: every casualty of this recovery cycle with the partition
        #: count it owned when it died — exactly one migrate_done is
        #: emitted per entry on success (the lint's 1:1 pairing).
        casualties: Dict[str, int] = {}
        while True:
            # Merge whatever the casualties' last replies already
            # relayed BEFORE dumping: the coordinator's ring (and the
            # trace) must show the dead worker's final waves.
            self._collector.flush()
            for name in pending:
                casualties[name] = len(self._map.partitions_of(name))
                dump = None
                if self._flight.armed:
                    # A SIGKILLed worker cannot dump its own ring; the
                    # coordinator dumps ITS ring — which contains the
                    # merged recent history, the casualty's relayed
                    # events included — named for the casualty.
                    dump = self._flight.dump(
                        f"worker_lost: {name} (epoch "
                        f"{self._map.epoch})",
                        name=f"{name}-coordinator")
                    if dump:
                        self.postmortems.append(dump)
                self._emit_lifecycle("worker_lost", worker=name,
                                     epoch=self._map.epoch, dump=dump)
                # The casualty's tier summary must not keep feeding
                # the coordinator's store aggregate (its spilled rows
                # are rebuilt into survivors' in-RAM sets by the
                # migration). NOT in _reap: the normal end-of-run
                # shutdown reaps every worker and the final stats must
                # keep their summaries.
                self._worker_store.pop(name, None)
                self._reap(name)
            survivors = self._membership.workers()
            if not survivors:
                raise _Abort("all workers lost; nothing to migrate to")
            if self._ckpt is None:
                raise _Abort(
                    "worker lost with no checkpoint_path: partitions "
                    "are unrecoverable (run with a checkpoint path for "
                    "elasticity)")
            old_map = self._map
            self._map = old_map.with_owners(survivors)
            gen = self._read_generation()
            try:
                self._assign_all(load_round=gen["round"])
            except _WorkerLost as e:
                pending = e.names
                continue
            # Counters rewind WITH the data — recovered totals cannot
            # double-count work redone since the generation.
            with self._lock:
                self._state_count = gen["state_count"]
                self._unique_count = gen["unique_count"]
                self._discoveries = dict(gen["discoveries"])
                # Tier summaries rewind with the data: every worker's
                # store was reset by the reassign, so stale spill
                # counts must not survive into the new epoch's
                # aggregate (the next round's replies repopulate).
                self._worker_store.clear()
            self._round = gen["round"]
            self._migrations += 1
            # Rotate the tracer run: cumulative wave counters rewind
            # with the rollback, and the lint's monotonicity invariant
            # is per run — a migration starts a new one, exactly as a
            # supervisor restart does (each attempt is its own run).
            # The collector flushes through the OLD tracer first (the
            # survivors' reassign replies carried their own rotation
            # markers), then follows the coordinator onto the new one
            # — cross-stream fault/recover pairing is file-order
            # global, so it survives the rotation by construction.
            self._collector.flush()
            if self._wave_obs.enabled:
                # Final snapshot into the closing run (cumulative
                # counts stay monotone within the new run too).
                self._wave_obs.close(self._tracer)
            self._tracer.close()
            self._tracer = tracer_from_env("elastic", meta={
                "model": type(self._model).__name__,
                "migrated_after": sorted(pending),
                "epoch": self._map.epoch})
            self._collector.tracer = self._tracer
            # Exactly ONE migrate_done per lost worker (the lint's 1:1
            # membership pairing): even a worker that owned nothing is
            # acknowledged, and two losses in one round get two. ``to``
            # names the survivor that adopted the plurality of the dead
            # worker's partitions (first survivor when it owned none).
            adopters: Dict[str, Dict[str, int]] = {}
            for p, (old, new) in self._map.moves_from(old_map).items():
                if old in casualties:
                    by = adopters.setdefault(old, {})
                    by[new] = by.get(new, 0) + 1
            for name in sorted(casualties):
                by = adopters.get(name, {})
                to = (max(sorted(by), key=by.get) if by
                      else survivors[0])
                self._emit_lifecycle("migrate_done",
                                     partitions=casualties[name],
                                     to=to, epoch=self._map.epoch)
            if self._tracer.enabled:
                # The migration IS the recovery: an injected
                # worker_crash fault pairs with this, exactly like a
                # supervised retry pairs with a wave_crash.
                self._tracer.event(
                    "recover", attempt=self._migrations, backoff_s=0.0,
                    resumed_from=self._ckpt, kind="migration",
                    _flush=True)
            return

    def _admit_join(self, name: str, sock) -> None:
        """Admits a registered joiner at a drained barrier: donors
        snapshot the partitions the joiner wins, the joiner loads them,
        donors drop them, the epoch bumps, and a fresh full generation
        lands so later rollbacks stay consistent. No rollback here —
        a join loses no work."""
        handle = self._members.get(name)
        if handle is None:
            handle = self._members[name] = _Handle(name, sock)
        else:
            handle.sock = sock
        self._membership.add(name)
        self._emit_lifecycle("worker_join", worker=name,
                             epoch=self._map.epoch)
        old_map = self._map
        new_map = old_map.with_owners(
            list(old_map.owners) + [name]
            if name not in old_map.owners else old_map.owners)
        moves = new_map.moves_from(old_map)
        if moves and self._ckpt is None:
            # No handoff medium: admit the worker but leave ownership
            # alone (it will win partitions at the next loss/epoch).
            self._map = old_map.with_assignment(old_map.assignment())
            return
        donors: Dict[str, List[int]] = {}
        for p, (old, _new) in sorted(moves.items()):
            donors.setdefault(old, []).append(p)
        for donor, ps in sorted(donors.items()):
            self._broadcast({"cmd": "checkpoint", "partitions": ps,
                             "path": self._ckpt, "round": self._round,
                             "epoch": old_map.epoch}, names=[donor])
        self._map = new_map
        moved = sorted(moves)
        replies = self._broadcast(
            {"cmd": "assign", "partitions": moved, "reset": True,
             "epoch": new_map.epoch,
             "load": {p: (self._ckpt, self._round) for p in moved}},
            names=[name])
        for donor, ps in sorted(donors.items()):
            self._broadcast({"cmd": "drop", "partitions": ps},
                            names=[donor])
        with self._lock:
            for p, r in replies[name]["queued"].items():
                self._queued[int(p)] = r
        self._rebalances += 1
        self._emit_lifecycle("rebalance", partitions=len(moved),
                             to=name, epoch=new_map.epoch)
        # A fresh generation at the new epoch: every later rollback
        # must see one consistent (manifest, shard files) cut that
        # already reflects the new ownership.
        self._write_generation(self._round)

    def _drain_joins(self) -> None:
        while True:
            try:
                name, sock = self._incoming.get_nowait()
            except queue.Empty:
                return
            try:
                self._admit_join(name, sock)
            except _WorkerLost as e:
                self._recover(e.names)
            except _Abort as e:
                # A failed admission (the joiner cannot load a donated
                # shard, a donor's handoff snapshot failed) must not
                # convert an ELECTIVE elasticity operation into total
                # run failure: the generations on disk are intact, so
                # treat the joiner as lost and recover — the rollback
                # re-derives ownership over the survivors, whichever
                # half-step the admission died at.
                if name not in self._members:
                    raise
                self.events.append({"type": "join_failed", "worker":
                                    name, "error": str(e)[:300],
                                    "t": time.monotonic()})
                self._recover([name])

    # -- The coordinated round loop ----------------------------------------

    def _run(self) -> None:
        try:
            self._run_rounds()
        except (_Abort, _WorkerLost) as e:
            # _WorkerLost escaping the recovery machinery (a loss
            # during startup seeding, before any generation exists to
            # migrate from) is terminal too: same public error type,
            # same acknowledged abort on the trace — never a silent
            # internal exception.
            dump = None
            if self._flight.armed:
                dump = self._flight.dump(f"abort: {e}")
                if dump:
                    self.postmortems.append(dump)
            if self._tracer.enabled:
                self._tracer.event("abort", reason=str(e)[:300],
                                   attempts=self._migrations,
                                   dump=dump, _flush=True)
            self._error = RuntimeError(str(e))
        except BaseException as e:  # noqa: BLE001 — surfaced at join()
            self._error = e
            if self._flight.armed:
                dump = self._flight.dump(f"{type(e).__name__}: {e}")
                if dump:
                    self.postmortems.append(dump)
        finally:
            # The stop replies carried each worker's final relay drain;
            # merge them before the stream closes.
            self._collector.flush()
            if self._wave_obs.enabled:
                self._wave_obs.close(self._tracer)
            self._tracer.close()
            self._done.set()

    def _run_rounds(self) -> None:
        initial = list(self._map.owners)
        for name in initial:
            self._spawn_worker(name)
        self._await_register(
            initial, time.monotonic() + self._spawn_timeout)
        if self._resume_from is not None:
            from ..checkpoint_format import PREV_SUFFIX

            gen = self._read_generation(self._resume_from)
            # Shard files always live beside the BASE manifest path:
            # a resume_from handed an explicit '...prev' manifest
            # (what newest_valid_checkpoint returns after a torn
            # write) must probe 'X.shardNNN(.prev)', not the
            # nonexistent 'X.prev.shardNNN'.
            base = self._resume_from
            if base.endswith(PREV_SUFFIX):
                base = base[:-len(PREV_SUFFIX)]
            self._assign_all(load_round=gen["round"], load_path=base)
            with self._lock:
                self._state_count = gen["state_count"]
                self._unique_count = gen["unique_count"]
                self._discoveries = dict(gen["discoveries"])
            self._round = gen["round"]
            # Re-establish a generation at THIS run's checkpoint path
            # (resume_from may be a different store): a worker lost
            # before the first post-resume cadence must migrate from
            # here, exactly like the seed path's generation 0.
            self._write_generation(self._round)
        else:
            self._assign_all(seeds=self._seed_blocks())
            # Generation 0 before any expansion: a worker lost before
            # the first cadence checkpoint still migrates (it rewinds
            # to the seed, not to nothing).
            self._write_generation(self._round)
        self.wave_log.append((time.monotonic(), self._state_count))

        while True:
            # Rest point: stop requests, scripted chaos, joins, lease
            # sweeps.
            if self._stop_req.is_set():
                break
            next_round = self._round + 1
            victim = self._kill_at.pop(next_round, None)
            if victim is not None:
                self.kill_worker(victim)
            joiner = self._join_at.pop(next_round, None)
            if joiner is not None:
                self._spawn_worker(joiner)
            self._drain_joins()
            expired = self._membership.expired()
            if expired:
                self._recover(expired)
                continue
            with self._lock:
                # The engine family's stop rule (bfs.rs:117 /
                # engine._run_waves): drained queues, every property
                # discovered, or the target cap — checked at the same
                # rest-point granularity the sharded host loop uses.
                done = (all(r == 0 for r in self._queued.values())
                        or len(self._discoveries) == self._n_properties
                        or (self._target is not None
                            and self._state_count >= self._target))
            if done:
                break
            try:
                self._one_round()
            except _WorkerLost as e:
                self._recover(e.names)
        self._final_workers = self._membership.workers()
        try:
            # The run is complete; a worker dying during the final
            # snapshot/goodbye loses nothing (totals are final and the
            # last cadence generation is on disk), so don't fail it.
            # A requested stop skips the final snapshot for promptness
            # (the last cadence generation already supports a resume).
            if not self._stop_req.is_set():
                self._write_generation(self._round)
            self._broadcast({"cmd": "stop"})
        except _WorkerLost:
            pass
        for name in list(self._members):
            self._reap(name)

    def _one_round(self) -> None:
        self._round += 1
        r = self._round
        replies = self._broadcast({"cmd": "wave", "round": r,
                                   "epoch": self._map.epoch})
        # Route every outbound block to its partition's CURRENT owner.
        # This is the epoch-aware hop: a block computed before a remap
        # never reaches a stale owner, because remaps only happen at
        # drained barriers (a loss discards the whole round instead).
        deliveries: Dict[str, Dict[int, list]] = {}
        successors = candidates = 0
        queued: Dict[int, int] = {}
        #: per-worker self-reported segment durations for this round —
        #: the straggler attribution's input (durations only: no
        #: cross-process clock ever gets compared).
        reports: Dict[str, dict] = {}
        for sender in sorted(replies):
            reply = replies[sender]
            successors += reply["successors"]
            candidates += reply["candidates"]
            queued.update({int(p): n
                           for p, n in reply["queued"].items()})
            if reply.get("store") is not None:
                self._worker_store[sender] = reply["store"]
            reports[sender] = {
                "compute_s": float(reply.get("compute_s") or 0.0),
                "successors": reply["successors"],
                "queued": sum(reply["queued"].values())}
            for p, block in reply["out"].items():
                owner = self._map.owner_of(int(p))
                deliveries.setdefault(owner, {}).setdefault(
                    int(p), []).append(block)
        novel = 0
        if deliveries:
            seq = self._next_seq()
            for name in sorted(deliveries):
                self._send(name, {"cmd": "deliver", "seq": seq,
                                  "blocks": deliveries[name]})
            for name in sorted(deliveries):
                reply = self._await_reply(name, seq)
                if not reply.get("ok"):
                    raise _Abort(f"worker {name}: "
                                 f"{reply.get('error', 'failed')}")
                novel += reply["novel"]
                queued.update({int(p): n
                               for p, n in reply["queued"].items()})
                if name in reports:
                    reports[name]["exchange_s"] = float(
                        reply.get("exchange_s") or 0.0)
                    reports[name]["queued"] = sum(
                        reply["queued"].values())
        # The round committed: apply counters and the wave event.
        hits: Dict[str, int] = {}
        for sender in sorted(replies):
            for prop, fp in replies[sender]["hits"].items():
                hits.setdefault(prop, fp)
        now = time.monotonic()
        with self._lock:
            self._state_count += successors
            self._unique_count += novel
            for prop, fp in hits.items():
                self._discoveries.setdefault(prop, fp)
            self._queued = queued
            self.wave_log.append((now, self._state_count))
            from ..checker.base import host_store_capacity

            capacity = host_store_capacity(self._unique_count)
            spilled = sum(s.get("spilled_rows", 0)
                          for s in self._worker_store.values())
            entry = {
                "t": now, "states": self._state_count,
                "unique": self._unique_count, "bucket": self._B,
                "waves": 1, "inflight": 0, "compiled": False,
                "successors": successors, "candidates": candidates,
                # Real store occupancy gauges (schema v6; permanent
                # nulls through v5): the run's visited store is the
                # union of the workers' host dicts, measured by the
                # same CPython growth policy the host engines report.
                "novel": novel, "out_rows": novel,
                "capacity": capacity,
                "load_factor": round(
                    max(0, self._unique_count - spilled) / capacity, 4),
                "overflow": False,
                "bytes_per_state": 4 * self._W, "arena_bytes": None,
                "table_bytes": 8 * self._unique_count,
                "tier_host_rows": max(0, self._unique_count - spilled),
                "tier_host_bytes": 8 * max(
                    0, self._unique_count - spilled),
                "tier_disk_rows": sum(
                    s.get("disk_rows", 0)
                    for s in self._worker_store.values()) or None,
                # v5 attribution: the coordinator's round summary is
                # positioned in the same (epoch, round) order its
                # workers' merged events use.
                "epoch": self._map.epoch, "round": r}
            self.dispatch_log.append(entry)
        if self._flight.armed:
            self._flight.record(entry)
        # Causal order in the merged file: the workers' round-r wave
        # events land BEFORE the coordinator's round-r summary that
        # folds them, then the straggler attribution for the round.
        self._collector.flush()
        if self._tracer.enabled:
            self._tracer.wave(entry)
        if self._wave_obs.enabled:
            # Straggler-wait hint for anomaly attribution: the round's
            # barrier waste is every worker's gap to the slowest one.
            computes = [float(rep.get("compute_s") or 0.0)
                        for rep in reports.values()]
            wait_hint = (len(computes) * max(computes) - sum(computes)
                         if computes else None)
            self._wave_obs.wave(entry, self._tracer, self._flight,
                                wait_s=wait_hint)
        self._collector.straggler(r, self._map.epoch, reports)
        if self._ckpt is not None and r % self._ckpt_every == 0:
            self._write_generation(r)

    # -- Live elasticity ---------------------------------------------------

    def kill_worker(self, name: str) -> None:
        """Kills a worker the hard way (SIGKILL for processes, vanish-
        at-next-command for threads); the coordinator discovers the
        death through its lease/EOF machinery and migrates — this is
        the preemption drill, not a graceful drain."""
        handle = self._members.get(name)
        if handle is None:
            raise ValueError(f"no such worker {name!r}")
        if handle.proc is not None:
            handle.proc.kill()
        elif handle.kill_event is not None:
            handle.kill_event.set()

    def stop(self) -> None:
        """Requests a prompt stop at the next round barrier (deadline
        cuts): workers are told to exit, no error is raised, counters
        reflect the committed rounds, and the last cadence generation
        stays on disk for a later ``resume_from``."""
        self._stop_req.set()

    def add_worker(self, name: Optional[str] = None) -> str:
        """Spawns a new worker that joins at the next round barrier
        (rendezvous rebalance, logged as a ``rebalance`` event)."""
        if name is None:
            name = f"w{self._next_worker}"
            self._next_worker += 1
        self._spawn_worker(name)
        return name

    # -- Checker facade ----------------------------------------------------

    def model(self):
        return self._model

    def state_count(self) -> int:
        with self._lock:
            return self._state_count

    def unique_state_count(self) -> int:
        with self._lock:
            return self._unique_count

    def discoveries(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._discoveries)

    @property
    def epoch(self) -> int:
        return self._map.epoch

    def workers(self) -> List[str]:
        """Live workers while running; the final membership once done
        (the coordinator reaps its sockets on completion)."""
        if self._done.is_set():
            return list(getattr(self, "_final_workers", []))
        return self._membership.workers()

    def scheduler_stats(self) -> dict:
        with self._lock:
            stats = {
                "elastic": {
                    "workers": self.workers(),
                    "n_partitions": self._n_parts,
                    "rounds": self._round,
                    "epoch": self._map.epoch,
                    "migrations": self._migrations,
                    "rebalances": self._rebalances,
                    "transport": self._transport,
                }
            }
            stats["store"] = {
                "enabled": bool(self._worker_store),
                "workers": dict(self._worker_store),
                "spilled_rows": sum(
                    s.get("spilled_rows", 0)
                    for s in self._worker_store.values()),
            }
        stats["elastic_obs"] = self.elastic_obs()
        stats["slo"] = self._wave_obs.slo_status()
        stats["anomalies"] = self._wave_obs.anomalies()
        return stats

    def elastic_obs(self) -> dict:
        """The distributed-observability aggregate: per-worker
        straggler gauges (compute/exchange/wait seconds, states/s,
        wait share), the slowest-worker histogram, trace-merge
        counters, heartbeat ages, and any postmortem dump paths.
        Cheap per call (reads the collector's running aggregates, not
        the event stream) — the explorer's ``GET /.metrics`` polls
        it."""
        obs = self._collector.summary()
        obs["postmortems"] = list(self.postmortems)
        obs["heartbeat_ages"] = (
            {} if self._done.is_set() else self._membership.ages())
        return obs

    def is_done(self) -> bool:
        return self._done.is_set()

    def join(self) -> "ElasticChecker":
        self._thread.join()
        self._accept_stop.set()
        try:
            self._listener.close()
        except OSError:
            pass
        if self._error is not None:
            raise self._error
        return self


def elastic_check(model_factory: Callable, **kwargs) -> ElasticChecker:
    """One-shot convenience: spawn, run to completion, return the
    joined checker."""
    return ElasticChecker(model_factory, **kwargs).join()
