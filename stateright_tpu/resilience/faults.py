"""Deterministic fault injection: the ``STpu_FAULTS`` registry.

Multi-hour runs on preemptible accelerators (the ROADMAP's production
north star) die in ways no happy-path suite exercises: a grow-time OOM,
a checkpoint torn mid-write, a dead measurement child, a corrupt
collective. This module makes every one of those failures *injectable
on demand, deterministically*, so the recovery paths (supervisor retry,
CRC'd checkpoint rotation, in-engine OOM degradation) are tested code,
not luck.

Spec grammar (the ``STpu_FAULTS`` environment variable)::

    STpu_FAULTS="grow_oom@n=1,torn_ckpt@n=2,wave_crash@n=12@times=2"

Comma-separated entries, each ``point[@key=value]...``:

- ``n=N``      fire starting at the Nth *hit* of the fault point
               (hits are counted per point, process-wide — replays of
               the same spec in the same process order fire at the
               same sites). Default 1. ``wave=N`` is an alias, reading
               naturally at wave-indexed sites.
- ``times=K``  fire on K consecutive eligible hits (default 1; ``0``
               means every eligible hit — e.g. a permanently-failing
               allocation).
- ``p=X``      Bernoulli(X) per hit instead of the deterministic
               window, drawn from a generator seeded by
               ``seed=S`` xor the point name — two runs with the same
               spec fire identically (replayable).

Fault *points* (see ``FAULT_POINTS``) are threaded through the four
device engines, the host BFS, the checkpoint writer, the sharded
all-to-all, and the bench device child. A point that is not armed costs
one attribute check (``plan.active``) — with ``STpu_FAULTS`` unset the
shared ``NULL_PLAN`` is returned and the hot loops pay nothing else
(same contract as the obs tracer; MEASUREMENTS round-10 pins the <1%
overhead).

Every firing emits a versioned ``fault`` obs event, and every recovery
path emits ``recover`` (or terminal ``abort``) — ``tools/trace_lint.py``
asserts the pairing over a captured stream.

Dependency-free beyond ``stateright_tpu.obs`` (no jax, no numpy): the
lint tool, the checkpoint writer, and the bench child all import this
without touching a backend.
"""

from __future__ import annotations

import os
import random
import threading
from typing import Dict, Optional

from ..obs.tracer import tracer_from_env

__all__ = [
    "FAULTS_ENV", "FAULT_POINTS", "InjectedFault", "InjectedOom",
    "ExchangeIntegrityError", "FaultPlan", "NULL_PLAN",
    "fault_plan_from_env", "reset_fault_plans", "is_oom",
]

#: Environment knob: a comma-separated fault spec (see module docstring).
#: Unset means the shared ``NULL_PLAN`` — hot loops pay one attribute
#: check.
FAULTS_ENV = "STpu_FAULTS"

#: The registry: every injectable site, with where its hook lives. A
#: spec naming an unknown point is rejected at parse time — a typo must
#: not silently disarm a chaos run.
FAULT_POINTS: Dict[str, str] = {
    "wave_crash": "engine wave loops (all four device engines): raise "
                  "while processing the Nth dispatch",
    "grow_oom": "visited-table/arena growth (all four device engines): "
                "simulated RESOURCE_EXHAUSTED at the Nth grow attempt",
    "torn_ckpt": "checkpoint writer: the Nth write dies mid-write, "
                 "leaving truncated bytes at the final path",
    "ckpt_crc": "checkpoint writer: the Nth write silently lands one "
                "corrupted section (lying disk; caught by the v3 CRCs)",
    "a2a_short": "sharded all-to-all: the Nth exchange delivers a short "
                 "shard block (tail rows missing)",
    "a2a_corrupt": "sharded all-to-all: the Nth exchange delivers a "
                   "corrupted fingerprint payload",
    "host_crash": "host BFS worker: raise in the Nth check block",
    "worker_crash": "elastic worker: die (hard-exit / abrupt socket "
                    "close) at the Nth coordinated round — the "
                    "coordinator's lease machinery must turn it into "
                    "worker_lost + migration, not an abort",
    "spill_fail": "tiered store: the Nth device->host visited spill "
                  "dies before any tier mutation — recovered by a "
                  "supervised checkpoint resume",
    "disk_full": "tiered store: the Nth cold write (visited segment "
                 "or frontier stash) raises at allocation (models "
                 "ENOSPC) — recovered by a supervised checkpoint "
                 "resume",
    "page_in_torn": "tiered store: the Nth cold-segment write lands "
                    "torn (truncated final path) — the store's "
                    "immediate CRC re-verify falls back to the "
                    "rotation predecessor and keeps the rows warm; "
                    "at a frontier page-in site it raises instead",
    "admit_fault": "overload controller: the Nth admission decision "
                   "raises mid-policy, BEFORE any job state mutates — "
                   "submission handling must fail that one request and "
                   "leak nothing (no half-admitted job, queue "
                   "unwedged, later submissions unaffected)",
    "preempt_wedge": "overload controller: the Nth controller-driven "
                     "park dies mid-actuation (models a wedged "
                     "checkpoint write at the drain rest point) — the "
                     "controller must survive its own crash, the "
                     "victim keeps running under its Supervisor, and "
                     "any park that does land still pairs with a "
                     "resume or terminal abort",
}


class InjectedFault(RuntimeError):
    """An injected failure (``STpu_FAULTS``). Deliberately a plain
    ``RuntimeError`` subclass: recovery code must treat it exactly like
    the organic failure it models."""


class InjectedOom(InjectedFault, MemoryError):
    """An injected allocation failure — caught by the same handlers
    that field a real ``RESOURCE_EXHAUSTED``/``MemoryError``."""


class ExchangeIntegrityError(RuntimeError):
    """A sharded all-to-all delivered a block that fails the owner-side
    integrity check (short rows or sentinel fingerprints in the
    payload). The wave's table insertions are already applied, so the
    in-memory frontier is torn — resume from the last checkpoint."""


def is_oom(err: BaseException) -> bool:
    """Whether ``err`` is an allocation failure worth degrading for:
    a ``MemoryError`` (incl. :class:`InjectedOom`) or a jax/XLA
    RESOURCE_EXHAUSTED, matched textually so this module never imports
    a backend."""
    if isinstance(err, MemoryError):
        return True
    text = f"{type(err).__name__}: {err}"
    return "RESOURCE_EXHAUSTED" in text or "Out of memory" in text


class _PointState:
    __slots__ = ("n", "times", "p", "rng", "hits", "fired")

    def __init__(self, n: int, times: int, p: Optional[float],
                 seed: int, point: str):
        self.n = n
        self.times = times
        self.p = p
        # Per-point stream: the same spec replays identically whatever
        # other points interleave.
        self.rng = random.Random(f"{seed}:{point}") if p is not None \
            else None
        self.hits = 0
        self.fired = 0


class FaultPlan:
    """A parsed ``STpu_FAULTS`` spec with per-point hit counters.

    Counters are process-wide per plan and plans are cached per spec
    string (:func:`fault_plan_from_env`), so a supervisor's respawned
    engine continues the SAME countdown — a ``times=1`` fault fires
    once per process, not once per engine instance (otherwise every
    recovery would re-fault identically and never converge).
    """

    active = True

    def __init__(self, spec: str):
        self.spec = spec
        self._lock = threading.Lock()
        self._points: Dict[str, _PointState] = {}
        self._tracer = None
        for entry in spec.split(","):
            entry = entry.strip()
            if not entry:
                continue
            parts = entry.split("@")
            point, kvs = parts[0].strip(), parts[1:]
            if point not in FAULT_POINTS:
                raise ValueError(
                    f"unknown fault point {point!r} in {FAULTS_ENV} "
                    f"(known: {sorted(FAULT_POINTS)})")
            n, times, p, seed = 1, 1, None, 0
            for kv in kvs:
                key, _, value = kv.partition("=")
                key, value = key.strip(), value.strip()
                if key in ("n", "wave"):
                    n = int(value)
                elif key == "times":
                    times = int(value)
                elif key == "p":
                    p = float(value)
                elif key == "seed":
                    seed = int(value)
                else:
                    raise ValueError(
                        f"unknown fault key {key!r} in {FAULTS_ENV} "
                        f"entry {entry!r} (known: n/wave, times, p, "
                        "seed)")
            if n < 1:
                raise ValueError(
                    f"fault point {point!r}: n must be >= 1")
            self._points[point] = _PointState(n, times, p, seed, point)

    def _decide(self, point: str) -> Optional[int]:
        """Counts one hit of ``point``; returns the hit index when the
        plan says fire, else None."""
        st = self._points.get(point)
        if st is None:
            return None
        with self._lock:
            st.hits += 1
            if st.hits < st.n:
                return None
            if st.times and st.fired >= st.times:
                return None
            if st.p is not None and st.rng.random() >= st.p:
                return None
            if st.p is None and st.times \
                    and st.hits >= st.n + st.times:
                return None
            st.fired += 1
            return st.hits

    def _emit(self, point: str, hit: int, mode: str, tracer,
              **ctx) -> None:
        if tracer is None or not tracer.enabled:
            # Sites without an engine tracer (the checkpoint writer,
            # the bench child) still record their firing. Created
            # under the plan lock: concurrent first firings from two
            # threads must not each open the stream (the loser's
            # run_start would orphan and its flusher thread leak).
            with self._lock:
                if self._tracer is None:
                    self._tracer = tracer_from_env(
                        "faults", meta={"spec": self.spec})
            tracer = self._tracer
        if tracer.enabled:
            # Always flushed: fault events are rare, several producers
            # append to one stream with independent buffers, and the
            # lint's fault->recover pairing reads FILE order — a
            # buffered fault draining after its recovery would read as
            # an unrecovered failure.
            tracer.event("fault", point=point, hit=hit, mode=mode,
                         _flush=True, **ctx)

    def crash(self, point: str, tracer=None, **ctx) -> None:
        """Raises :class:`InjectedFault` (or :class:`InjectedOom` for
        ``grow_oom``) when the plan fires at this hit; a no-op
        otherwise."""
        hit = self._decide(point)
        if hit is None:
            return
        if point == "grow_oom":
            self._emit(point, hit, "oom", tracer, **ctx)
            raise InjectedOom(
                f"injected RESOURCE_EXHAUSTED at fault point "
                f"{point!r} (hit {hit})")
        self._emit(point, hit, "raise", tracer, **ctx)
        raise InjectedFault(
            f"injected crash at fault point {point!r} (hit {hit})")

    def fires(self, point: str, tracer=None, mode: str = "corrupt",
              **ctx) -> bool:
        """Counts a hit and reports whether the caller should apply the
        point's corruption/exit behavior (used by sites whose fault is
        data damage rather than an exception)."""
        hit = self._decide(point)
        if hit is None:
            return False
        self._emit(point, hit, mode, tracer, **ctx)
        return True

    def close(self) -> None:
        with self._lock:
            tracer, self._tracer = self._tracer, None
        if tracer is not None:
            tracer.close()


class _NullPlan:
    """The disarmed plan: ``active`` is False and every probe is a
    no-op. Hot loops guard with ``if plan.active:`` — one attribute
    check per wave, exactly the null-tracer contract."""

    __slots__ = ()
    active = False
    spec = ""

    def crash(self, point, tracer=None, **ctx) -> None:
        pass

    def fires(self, point, tracer=None, mode="corrupt", **ctx) -> bool:
        return False

    def close(self) -> None:
        pass


#: The shared disarmed plan (identity-testable, like ``NULL_TRACER``).
NULL_PLAN = _NullPlan()

#: spec string -> live plan. Cached so hit counters survive engine
#: re-creation (supervisor respawns) within one process.
_PLANS: Dict[str, FaultPlan] = {}
_PLANS_LOCK = threading.Lock()


def fault_plan_from_env(spec: Optional[str] = None):
    """The plan factory every site uses: ``STpu_FAULTS`` set means the
    (process-cached) live plan for that spec; unset means
    ``NULL_PLAN``."""
    spec = os.environ.get(FAULTS_ENV, "") if spec is None else spec
    if not spec:
        return NULL_PLAN
    with _PLANS_LOCK:
        plan = _PLANS.get(spec)
        if plan is None:
            plan = _PLANS[spec] = FaultPlan(spec)
        return plan


def reset_fault_plans() -> None:
    """Drops every cached plan (fresh hit counters). Test isolation
    only: two tests arming the same spec string must not share a
    consumed countdown."""
    with _PLANS_LOCK:
        for plan in _PLANS.values():
            plan.close()
        _PLANS.clear()
