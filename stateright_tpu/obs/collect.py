"""Distributed trace collection for the elastic runtime (schema v5).

Round 8's ``RunTracer`` assumes its producer can reach the trace file;
the elastic runtime's workers frequently cannot (a process-transport
worker on another host in the deployment this models), and even when
they can, N appenders racing one file give no causal order. This
module is the distributed half of ``obs``:

- :class:`RelayTracer` — the worker-side tracer. Same emitting surface
  as ``RunTracer`` (``wave`` / ``event`` / ``counter`` / ``gauge`` /
  ``span``), but events are stamped and **buffered in a bounded
  in-memory queue** instead of written; the worker's command loop
  drains them in bounded batches piggybacked on its round replies
  (zero extra round trips — the reply was going to the coordinator
  anyway). Every event is stamped with the worker name and a
  process-lifetime ``seq`` that survives run-id rotation, which is
  what makes downstream merge order and lint invariants possible.
  An optional ``mirror`` callable tees every stamped event into the
  worker's flight-recorder ring, so postmortems see the same stream
  the coordinator does.
- :class:`TraceCollector` — the coordinator side. Receives each
  worker's batches, assigns every event an effective ``(epoch,
  round)`` (non-wave events inherit their worker's last wave position,
  so rotation markers cannot sort ahead of the waves they follow),
  and flushes one causally-ordered merge — sorted by ``(epoch, round,
  worker, seq)`` — into the coordinator's trace file via
  ``RunTracer.emit_raw``. It also owns **straggler attribution**: per
  round, the workers' self-reported segment timings (compute,
  exchange) become barrier-wait times against the slowest worker
  (clock-skew-free: only durations cross the wire, never timestamps),
  emitted as a ``straggler`` event and aggregated for
  ``scheduler_stats()["elastic_obs"]`` / bench / ``GET /.metrics``.

Dependency-free beyond ``obs`` itself (no jax, no numpy): worker
processes import this before their backend exists.
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Callable, Dict, List, Optional, Tuple

from .schema import SCHEMA_VERSION, WAVE_NULL_DEFAULTS

__all__ = ["RelayTracer", "TraceCollector"]


class RelayTracer:
    """A ``RunTracer``-shaped emitter that buffers stamped events for
    relay instead of writing a file.

    ``buffering=False`` keeps the stamping/mirroring path (the flight
    recorder is always on) but queues nothing — the coordinator runs
    untraced, so shipping events nobody will write would be pure
    overhead. ``rotate()`` starts a new run id (the migration /
    reassignment story: cumulative counters rewind with a rollback and
    the lint's monotonicity is per run), while ``seq`` keeps counting
    across rotations so per-worker order is globally checkable.
    """

    enabled = True

    #: bounded-batch knobs: the buffer never grows past ``capacity``
    #: (oldest dropped, counted) and one reply carries at most
    #: ``batch`` events.
    _CAPACITY = 4096
    _BATCH = 256

    def __init__(self, worker: str, engine: str = "elastic_worker",
                 buffering: bool = True,
                 mirror: Optional[Callable[[dict], None]] = None,
                 meta: Optional[dict] = None):
        self.worker = str(worker)
        self.engine = engine
        self._buffering = bool(buffering)
        self._mirror = mirror
        self._lock = threading.Lock()
        self._local = threading.local()
        self._buf: deque = deque()
        self._seq = 0
        self._rotation = -1
        self._wave_index = 0
        self._counters: Dict[str, float] = {}
        self._dropped = 0
        self.run = ""
        self._start_run(meta)  # also sets self._t0

    # -- Run lifecycle -----------------------------------------------------

    def _start_run(self, meta: Optional[dict]) -> None:
        self._rotation += 1
        self.run = f"{self.worker}-{os.getpid():x}-{self._rotation}"
        self._wave_index = 0
        self._counters = {}
        self._t0 = time.monotonic()  # run_end durations are per run
        self._push({"type": "run_start", "unix_t": round(time.time(), 3),
                    "meta": dict(meta or {}, worker=self.worker)})

    def rotate(self, meta: Optional[dict] = None) -> None:
        """Ends the current run and starts a fresh one (same worker,
        same seq stream). Called at every partition reassignment —
        rollback migration, join handoff, donor drop — because each
        rewinds or re-bases the cumulative counters the lint checks
        per run."""
        self._end_run()
        self._start_run(meta)

    def _end_run(self) -> None:
        with self._lock:
            counters = dict(self._counters)
        self._push({"type": "run_end",
                    "dur": round(time.monotonic() - self._t0, 6),
                    "counters": counters})

    def close(self) -> None:
        self._end_run()

    # -- Plumbing ----------------------------------------------------------

    def _push(self, fields: dict, number_wave: bool = False) -> None:
        evt = {"schema_version": SCHEMA_VERSION, "engine": self.engine,
               "run": self.run, "worker": self.worker}
        evt.update(fields)
        evt.setdefault("t", round(time.monotonic(), 6))
        with self._lock:
            if number_wave:
                # Wave index and seq are stamped under the SAME lock
                # hold: two emitting threads (the wave loop + the
                # async-I/O writer) must never take wave indices in one
                # order and seqs in the other — the lint's per-worker
                # seq monotonicity and wave contiguity both key off
                # this pairing.
                evt["wave"] = self._wave_index
                self._wave_index += 1
            self._seq += 1
            evt["seq"] = self._seq
            if self._buffering:
                if len(self._buf) >= self._CAPACITY:
                    self._buf.popleft()
                    self._dropped += 1
                self._buf.append(evt)
        if self._mirror is not None:
            self._mirror(evt)

    def drain(self, limit: Optional[int] = None) -> Tuple[List[dict], int]:
        """Up to ``limit`` buffered events (FIFO — per-worker seq order
        is the merge contract) plus the count of events dropped to the
        capacity bound since the last drain."""
        limit = self._BATCH if limit is None else int(limit)
        out: List[dict] = []
        with self._lock:
            while self._buf and len(out) < limit:
                out.append(self._buf.popleft())
            dropped, self._dropped = self._dropped, 0
        return out, dropped

    # -- Emitters (RunTracer surface) --------------------------------------

    def wave(self, fields: dict) -> None:
        evt = dict(fields, type="wave")
        for key in WAVE_NULL_DEFAULTS:
            if key in ("worker", "seq"):
                continue  # _push stamps them
            evt.setdefault(key, None)
        self._push(evt, number_wave=True)

    def event(self, etype: str, **fields) -> None:
        fields.pop("_flush", None)
        self._push(dict(fields, type=etype))

    def counter(self, name: str, inc=1) -> None:
        with self._lock:
            total = self._counters.get(name, 0) + inc
            self._counters[name] = total
        self._push({"type": "counter", "name": name, "value": total,
                    "inc": inc})

    def gauge(self, name: str, value) -> None:
        self._push({"type": "gauge", "name": name, "value": value})

    def span_event(self, name: str, start: float, dur: float,
                   depth: int = 0, **attrs) -> None:
        evt = {"type": "span", "name": name, "t": round(start, 6),
               "dur": round(dur, 6), "depth": depth}
        if attrs:
            evt["attrs"] = attrs
        self._push(evt)

    @contextmanager
    def span(self, name: str, **attrs):
        depth = getattr(self._local, "depth", 0)
        self._local.depth = depth + 1
        start = time.monotonic()
        try:
            yield
        finally:
            self._local.depth = depth
            self.span_event(name, start, time.monotonic() - start,
                            depth=depth, **attrs)


class TraceCollector:
    """Merges relayed per-worker streams into the coordinator's trace
    and attributes per-round straggler cost.

    ``tracer`` is the coordinator's live ``RunTracer`` (reassignable —
    a migration rotates it); ``flight`` is the coordinator's flight
    recorder, which sees every merged event so a ``worker_lost``
    postmortem contains the casualty's own last relayed events.
    """

    def __init__(self, tracer, flight=None, obs=None):
        self.tracer = tracer
        self.flight = flight
        #: optional ``WaveObs`` facade (obs/hist.py): the straggler
        #: fold feeds each worker-round segment's compute/wait seconds
        #: into the elastic latency histograms — the attribution is
        #: computed here anyway, so armed cost is two observes per
        #: worker-round and disarmed cost is one attribute check.
        self.obs = obs
        self._lock = threading.Lock()
        #: (epoch, round, worker, seq, evt) awaiting the next flush.
        self._pending: List[tuple] = []
        #: per-worker carried position: non-wave events (rotation
        #: markers, spans) inherit their worker's last wave (epoch,
        #: round) so a global sort cannot reorder them ahead of it.
        self._last_pos: Dict[str, Tuple[int, int]] = {}
        self._last_seq: Dict[str, int] = {}
        self.merged = 0
        self.dropped = 0
        # Straggler aggregates (fed by ``straggler``).
        self._rounds_timed = 0
        self._max_wait_share = 0.0
        self._slowest_counts: Dict[str, int] = {}
        self._worker_totals: Dict[str, dict] = {}
        self._last_round: Optional[dict] = None

    # -- Merge -------------------------------------------------------------

    def add_batch(self, worker: str, events: List[dict],
                  dropped: int = 0) -> None:
        """Buffers one worker's relayed batch (already in that
        worker's seq order — the relay drains FIFO)."""
        if not events and not dropped:
            return
        with self._lock:
            self.dropped += int(dropped)
            pos = self._last_pos.get(worker, (-1, -1))
            for evt in events:
                if not isinstance(evt, dict):
                    continue
                epoch, rnd = evt.get("epoch"), evt.get("round")
                if isinstance(epoch, int) and isinstance(rnd, int):
                    pos = (epoch, rnd)
                seq = evt.get("seq")
                seq = seq if isinstance(seq, int) \
                    else self._last_seq.get(worker, 0) + 1
                self._last_seq[worker] = seq
                self._pending.append((pos[0], pos[1], str(worker), seq,
                                      evt))
            self._last_pos[worker] = pos

    def flush(self) -> int:
        """Writes every buffered event in ``(epoch, round, worker,
        seq)`` order through the current tracer (and the flight ring).
        Called at round barriers, before tracer rotation, and at run
        end; returns the number of events written."""
        with self._lock:
            pending, self._pending = self._pending, []
        if not pending:
            return 0
        pending.sort(key=lambda item: item[:4])
        tracer = self.tracer
        flight = self.flight
        for _, _, _, _, evt in pending:
            if tracer is not None and tracer.enabled:
                tracer.emit_raw(evt)
            if flight is not None and flight.armed:
                flight.record(evt)
        self.merged += len(pending)
        return len(pending)

    # -- Straggler attribution ---------------------------------------------

    def straggler(self, round_: int, epoch: int,
                  reports: Dict[str, dict]) -> Optional[dict]:
        """Folds one round's worker self-reports into the straggler
        record: per-worker compute/exchange/barrier-wait seconds,
        per-shard throughput and load share, the round's slowest
        worker, and the wait-time share (fraction of total worker-time
        the barrier burned — the multi-worker killer the GPUexplore
        scalability study measures). Durations are worker-local, so no
        cross-process clock comparison happens anywhere."""
        if not reports:
            return None
        computes = {w: float(r.get("compute_s") or 0.0)
                    for w, r in reports.items()}
        max_compute = max(computes.values())
        slowest = max(sorted(computes), key=computes.get)
        total_queued = sum(int(r.get("queued") or 0)
                           for r in reports.values())
        workers: Dict[str, dict] = {}
        wait_total = 0.0
        for w, rep in sorted(reports.items()):
            wait = max(0.0, max_compute - computes[w])
            wait_total += wait
            workers[w] = {
                "compute_s": round(computes[w], 6),
                "exchange_s": round(float(rep.get("exchange_s")
                                          or 0.0), 6),
                "wait_s": round(wait, 6),
                "states_s": round(int(rep.get("successors") or 0)
                                  / computes[w], 1)
                if computes[w] > 0 else 0.0,
                "load_share": round(int(rep.get("queued") or 0)
                                    / total_queued, 4)
                if total_queued else 0.0,
            }
        wait_share = (wait_total / (len(reports) * max_compute)
                      if max_compute > 0 else 0.0)
        record = {"round": int(round_), "epoch": int(epoch),
                  "slowest": slowest,
                  "wait_share": round(wait_share, 4),
                  "workers": workers}
        with self._lock:
            self._rounds_timed += 1
            self._max_wait_share = max(self._max_wait_share,
                                       record["wait_share"])
            self._slowest_counts[slowest] = \
                self._slowest_counts.get(slowest, 0) + 1
            self._last_round = record
            for w, seg in workers.items():
                tot = self._worker_totals.setdefault(
                    w, {"waves": 0, "compute_s": 0.0, "exchange_s": 0.0,
                        "wait_s": 0.0, "successors": 0})
                tot["waves"] += 1
                tot["compute_s"] += seg["compute_s"]
                tot["exchange_s"] += seg["exchange_s"]
                tot["wait_s"] += seg["wait_s"]
                tot["successors"] += int(
                    reports[w].get("successors") or 0)
        if self.obs is not None and self.obs.enabled:
            for w, seg in workers.items():
                self.obs.elastic_report(w, seg["compute_s"],
                                        seg["wait_s"])
        tracer = self.tracer
        if tracer is not None and tracer.enabled:
            tracer.event("straggler", **record)
        if self.flight is not None and self.flight.armed:
            self.flight.record_event("straggler", **record)
        return record

    def summary(self) -> dict:
        """The aggregated view bench / ``scheduler_stats`` /
        ``GET /.metrics`` surface as ``elastic_obs``."""
        with self._lock:
            workers = {}
            for w, tot in sorted(self._worker_totals.items()):
                busy = tot["compute_s"] + tot["wait_s"]
                workers[w] = {
                    "waves": tot["waves"],
                    "compute_s": round(tot["compute_s"], 6),
                    "exchange_s": round(tot["exchange_s"], 6),
                    "wait_s": round(tot["wait_s"], 6),
                    "states_s": round(tot["successors"]
                                      / tot["compute_s"], 1)
                    if tot["compute_s"] > 0 else 0.0,
                    "wait_share": round(tot["wait_s"] / busy, 4)
                    if busy > 0 else 0.0,
                }
            return {
                "rounds_timed": self._rounds_timed,
                "max_wait_share": round(self._max_wait_share, 4),
                "slowest": dict(sorted(self._slowest_counts.items())),
                "workers": workers,
                "last_round": self._last_round,
                "merged_events": self.merged,
                "dropped_events": self.dropped,
            }
