"""``JobService``: the multi-tenant checking scheduler.

A job names a corpus model (``service/registry.py``) plus parameters,
an engine (``classic`` / ``fused`` device engines, or ``host`` BFS),
and a small allowlisted knob set. Jobs queue into a bounded worker
pool; each runs under the round-10 :class:`Supervisor` with

- its **own checkpoint generation** (``<data_dir>/<job>.ckpt.npz``,
  format v5 with keep-last-2 rotation) — crash retries resume from the
  newest valid snapshot, and a *preempted* job (``DELETE /jobs/<id>``
  → the engine's cooperative ``preempt()``) leaves a resumable image a
  resubmission (``{"resume": "<id>"}``) continues bit-identically;
- its **own trace stream** (``<data_dir>/<job>.trace.jsonl``): the
  service emits the v7 ``job_submit``/``job_done``/``job_abort``
  lifecycle events and the engine appends its run there (worker-tagged
  run ids from obs v5 mean even interleaved producers separate), so
  ``GET /jobs/<id>/trace`` is a file read and ``tools/trace_lint.py``
  validates each job end to end;
- the **shared wave-program cache** (``jit_cache.WaveProgramCache``)
  keyed by the registry's ``(model, canonical params)`` — the Nth
  submission of a hot model skips XLA compilation entirely, surfaced
  per job (``jit_cache`` in the status payload) and in the service
  metrics.

Round 16 adds **cross-job wave multiplexing**: concurrent jobs of the
same corpus shape — same canonical ``(model, params)`` registry key,
same engine, same knob set — are admitted as tenants of one shared
:class:`~stateright_tpu.service.mux.MuxGroup`, whose waves batch the
tenants' frontiers into ONE device dispatch (``service/mux.py``). The
per-job surfaces (``GET /jobs/<id>`` counters, verdicts, checkpoint
bytes, trace stream) stay exactly what a solo engine produces. The
queue itself grew scheduling policy: ``priority`` (higher first, FIFO
within), per-``tenant`` running quotas honored at queue POP, and a
bounded depth whose overflow maps to HTTP 429 (:class:`JobQueueFull`).

Scope honesty (ARCHITECTURE "Elasticity"): the pool schedules jobs
across OS threads of ONE process on one host — the same
single-host scope as the elastic runtime's process workers. Multi-host
serving is not claimed here.
"""

from __future__ import annotations

import os
import tempfile
import threading
import time
from typing import Dict, List, Optional, Tuple

from ..jit_cache import WaveProgramCache
from ..obs.hist import prometheus_hist_lines, wave_obs_from_env
from ..obs.tracer import RunTracer
from ..resilience.supervisor import Supervisor, newest_valid_checkpoint
from .control import control_from_env
from .registry import ModelRegistry, default_registry

__all__ = ["Job", "JobService", "JobError", "JobConflict",
           "JobQueueFull", "JobShed"]

#: engine knobs a submission may set, with their coercion types —
#: everything else in the engine signature is the service's business
#: (checkpoint/trace paths, program cache), not the tenant's.
_KNOBS = {
    "batch_size": int,
    "max_batch_size": int,
    "table_capacity": int,
    "target_state_count": int,
    "checkpoint_every_waves": int,
    "waves_per_dispatch": int,
    "pack_arena": bool,
    "succ_ladder": bool,
    # Background host I/O (round 17): bit-identical either way; the
    # mux shape key includes it, so mixed-knob jobs never share a
    # group with the wrong writer policy.
    "async_io": bool,
}

_ENGINES = ("classic", "fused", "host")


class JobError(ValueError):
    """A submission the service rejects (HTTP 400)."""


class JobConflict(RuntimeError):
    """A valid request the job's current state cannot honor (409)."""


class JobQueueFull(RuntimeError):
    """Admission control: the bounded queue is at capacity (429)."""


class JobShed(JobQueueFull):
    """Round 21: the overload controller shed this submission (429 +
    ``Retry-After``). Subclasses :class:`JobQueueFull` so pre-round-21
    callers that catch-and-retry on queue pressure keep working; the
    extra fields carry the machine-readable reason and the
    drain-derived retry hint the HTTP layer surfaces."""

    def __init__(self, reason: str, retry_after_s: float):
        super().__init__(
            f"submission shed by overload controller ({reason}); "
            f"retry after {retry_after_s}s")
        self.reason = reason
        self.retry_after_s = retry_after_s


#: Priority aging (round 21): a queued entry gains one effective
#: priority level per ``_AGE_EVERY_POPS`` jobs dispatched past it, up
#: to ``_AGE_MAX_BOOST`` levels. The clock is POP COUNT, not wall time
#: — deterministic under test and proportional to actual bypass, so a
#: saturated high-priority stream can delay a low-priority job by at
#: most ``_AGE_EVERY_POPS * (gap + _AGE_MAX_BOOST)`` dispatches, never
#: forever. Ties at equal effective priority stay FIFO.
_AGE_EVERY_POPS = 4
_AGE_MAX_BOOST = 8


class _JobQueue:
    """The scheduler's queue: priority-ordered (higher first, FIFO
    within a priority, with bounded pop-count aging so a saturated
    high-priority stream cannot starve low priorities forever),
    bounded (``put`` raises :class:`JobQueueFull` at capacity), with
    per-tenant RUNNING quotas enforced at pop — a tenant at quota is
    skipped, not starved: its entries stay in place and become
    eligible the moment one of its jobs finishes. The overload
    controller's brownout rung 3 sets a HOLD floor: entries whose base
    priority is below it are paused in place (skipped, not dropped)
    until the ladder steps back up.

    The queue owns its own condition variable and tracks active
    counts internally (``task_done``), so the pop path never needs the
    service lock — the lock-ordering hazard of a worker blocking on
    the queue while holding service state simply cannot arise."""

    def __init__(self, max_queued: Optional[int] = None,
                 tenant_quota: Optional[int] = None):
        self._cv = threading.Condition()
        self._items: List[tuple] = []
        self._seq = 0
        self._max = max_queued
        self._quota = tenant_quota
        self._active: Dict[str, int] = {}
        self._closed = False
        self._pops = 0
        self._hold: Optional[int] = None

    def put(self, job_id: str, tenant: Optional[str] = None,
            priority: int = 0) -> None:
        with self._cv:
            if self._max is not None and len(self._items) >= self._max:
                raise JobQueueFull(
                    f"job queue is full ({len(self._items)}/"
                    f"{self._max}); retry after a job finishes")
            self._seq += 1
            self._items.append((-int(priority), self._seq, job_id,
                                tenant, self._pops))
            self._items.sort()
            self._cv.notify()

    def set_hold(self, threshold: Optional[int]) -> None:
        """Brownout rung 3 actuator: pause (don't drop) queued entries
        whose BASE priority is below ``threshold``; ``None`` releases
        the hold. Held entries keep their seq and aging credit."""
        with self._cv:
            self._hold = threshold
            self._cv.notify_all()

    def pop(self) -> Optional[Tuple[str, Optional[str]]]:
        """Blocks for the next runnable entry; ``None`` means the
        queue closed. The caller MUST pair a non-None pop with ONE
        ``task_done(tenant)`` once the job leaves "running". Selection
        is by EFFECTIVE priority — base plus the bounded age boost —
        with FIFO tie-break, over entries passing the quota and hold
        filters."""
        with self._cv:
            while True:
                if self._closed:
                    return None
                best_i, best_key = -1, None
                for i, (neg_pri, seq, job_id, tenant,
                        born) in enumerate(self._items):
                    if self._hold is not None and -neg_pri < self._hold:
                        continue
                    if (self._quota is not None and tenant is not None
                            and self._active.get(tenant, 0)
                            >= self._quota):
                        continue
                    boost = min(_AGE_MAX_BOOST,
                                (self._pops - born) // _AGE_EVERY_POPS)
                    key = (-neg_pri + boost, -seq)
                    if best_key is None or key > best_key:
                        best_i, best_key = i, key
                if best_i >= 0:
                    _, _, job_id, tenant, _ = self._items.pop(best_i)
                    self._pops += 1
                    if tenant is not None:
                        self._active[tenant] = \
                            self._active.get(tenant, 0) + 1
                    return job_id, tenant
                self._cv.wait(timeout=0.5)

    def task_done(self, tenant: Optional[str]) -> None:
        with self._cv:
            if tenant is not None:
                count = self._active.get(tenant, 0) - 1
                if count > 0:
                    self._active[tenant] = count
                else:
                    self._active.pop(tenant, None)
            self._cv.notify_all()

    def cancel(self, job_id: str) -> bool:
        """Removes a still-queued entry (``DELETE`` on a queued job)."""
        with self._cv:
            for i, item in enumerate(self._items):
                if item[2] == job_id:
                    self._items.pop(i)
                    return True
            return False

    def qsize(self) -> int:
        with self._cv:
            return len(self._items)

    def close(self) -> None:
        with self._cv:
            self._closed = True
            self._cv.notify_all()


class Job:
    """One submission's record. All mutation happens under the
    service lock; the engine reference is read lock-free for live
    counters (its count methods are thread-safe)."""

    def __init__(self, job_id: str, spec: dict, trace_path: str,
                 checkpoint_path: Optional[str]):
        self.id = job_id
        self.spec = spec
        self.trace_path = trace_path
        self.checkpoint_path = checkpoint_path
        self.state = "queued"
        self.error: Optional[str] = None
        self.resume_of: Optional[str] = None
        self.submitted_t = time.monotonic()
        self.started_t: Optional[float] = None
        self.finished_t: Optional[float] = None
        self.checker = None
        self.model = None
        self.resumed_by: Optional[str] = None
        self.preempt_requested = False
        self.tracer: Optional[RunTracer] = None
        self.result: Dict = {}
        #: the canonical registry cache key, computed ONCE at submit —
        #: the status-poll and engine-build paths read this instead of
        #: re-canonicalizing the params dict per request.
        self.program_key: Optional[tuple] = None

    def runtime(self) -> Optional[float]:
        if self.started_t is None:
            return None
        end = self.finished_t if self.finished_t is not None \
            else time.monotonic()
        return end - self.started_t


class JobService:
    """The scheduler: ``workers`` daemon threads drain a FIFO queue.
    ``data_dir`` holds per-job checkpoints and traces (a fresh temp
    dir by default); ``program_cache`` is shared across every device
    job the service runs."""

    def __init__(self, registry: Optional[ModelRegistry] = None,
                 workers: int = 2, data_dir: Optional[str] = None,
                 program_cache: Optional[WaveProgramCache] = None,
                 mux: bool = True, mux_max_jobs: int = 8,
                 max_queued: Optional[int] = None,
                 tenant_quota: Optional[int] = None,
                 control=None):
        self.registry = registry or default_registry()
        self.data_dir = data_dir or tempfile.mkdtemp(
            prefix="stpu-service-")
        os.makedirs(self.data_dir, exist_ok=True)
        self.program_cache = program_cache or WaveProgramCache()
        self._lock = threading.Lock()
        self._jobs: Dict[str, Job] = {}
        self._order: List[str] = []
        self._seq = 0
        self._queue = _JobQueue(max_queued=max_queued,
                                tenant_quota=tenant_quota)
        self._mux = bool(mux)
        self._mux_max_jobs = max(1, int(mux_max_jobs))
        self._mux_lock = threading.Lock()
        #: open group per corpus shape — (program_key, engine, knobs);
        #: closed groups are replaced lazily on the next admission.
        self._mux_groups: Dict[tuple, object] = {}
        self._mux_all: List[object] = []
        #: service observability (obs/hist.py): job queue/run/total
        #: latency histograms + the service SLO surface (/.healthz).
        #: Disarmed = the shared NULL_OBS (zero per-job cost).
        self._obs = wave_obs_from_env("service")
        #: round-21 overload controller: STpu_CONTROL (or an explicit
        #: instance) arms the closed loop; disarmed = NULL_CONTROL,
        #: and every hot-path consult is behind an `.armed` check.
        self._control = control if control is not None \
            else control_from_env()
        if self._control.armed:
            self._control.bind(self)
        self._workers = [
            threading.Thread(target=self._worker_loop, daemon=True,
                             name=f"stpu-job-worker-{i}")
            for i in range(max(1, int(workers)))]
        for t in self._workers:
            t.start()

    # -- Submission --------------------------------------------------------

    def submit(self, spec: dict) -> dict:
        """Validates and enqueues one job; returns its status payload.
        ``spec`` keys: ``model`` (+ optional ``params``), optional
        ``engine`` (default ``classic``), ``knobs``, ``properties``
        (verdict selection), ``priority`` (int; higher pops first),
        ``tenant`` (quota label for the pop-time running cap), or
        ``resume`` naming an earlier preempted/failed job to continue
        from its checkpoint generation. Raises :class:`JobQueueFull`
        (HTTP 429) when the bounded queue is at capacity."""
        if not isinstance(spec, dict):
            raise JobError("job spec must be a JSON object")
        resume_of: Optional[Job] = None
        if spec.get("resume") is not None:
            resume_of = self._job(str(spec["resume"]))
            with self._lock:
                if resume_of.state not in ("preempted", "failed"):
                    raise JobConflict(
                        f"job {resume_of.id} is {resume_of.state}; only "
                        "preempted/failed jobs can be resumed")
                if resume_of.checkpoint_path is None:
                    raise JobConflict(
                        f"job {resume_of.id} has no checkpoint to "
                        "resume from (host-engine jobs are not "
                        "resumable)")
            base = dict(resume_of.spec)
            base.update({k: v for k, v in spec.items() if k != "resume"})
            spec = base

        model_name = spec.get("model")
        if not isinstance(model_name, str):
            raise JobError("job spec needs a 'model' (corpus name); "
                           f"registered: {self.registry.names()}")
        engine = spec.get("engine", "classic")
        if engine not in _ENGINES:
            raise JobError(f"engine must be one of {_ENGINES}, "
                           f"got {engine!r}")
        try:
            model, params = self.registry.build(model_name,
                                                spec.get("params"))
        except KeyError as e:
            raise JobError(str(e)) from e
        except ValueError as e:
            raise JobError(str(e)) from e
        knobs = self._check_knobs(spec.get("knobs"))
        prop_names = [p.name for p in model.properties()]
        selected = spec.get("properties")
        if selected is not None:
            unknown = [p for p in selected if p not in prop_names]
            if unknown:
                raise JobError(
                    f"model {model_name!r} has no properties {unknown}; "
                    f"available: {prop_names}")
        if engine != "host" and getattr(model, "device_model",
                                        None) is None:
            raise JobError(
                f"model {model_name!r} has no device form; submit with "
                "engine='host'")

        try:
            priority = int(spec.get("priority", 0) or 0)
        except (TypeError, ValueError) as e:
            raise JobError(f"priority must be an integer: {e}") from e
        tenant = spec.get("tenant")
        if tenant is not None and not isinstance(tenant, str):
            raise JobError("tenant must be a string label")
        deadline_s = spec.get("deadline_s")
        if deadline_s is not None:
            try:
                deadline_s = float(deadline_s)
            except (TypeError, ValueError) as e:
                raise JobError(
                    f"deadline_s must be a number of seconds: {e}"
                ) from e
            if deadline_s <= 0:
                raise JobError("deadline_s must be > 0")

        # Overload admission (round 21): consulted BEFORE the job
        # record exists, so a shed allocates nothing and rolls back
        # nothing. Resumes bypass the gate — a parked job re-entering
        # is the controller DRAINING pressure, not new demand.
        if self._control.armed and resume_of is None:
            decision = self._control.admission(
                tenant, priority, self._queue.qsize())
            if decision is not None:
                raise JobShed(*decision)

        clean_spec = {"model": model_name, "params": params,
                      "engine": engine, "knobs": knobs,
                      "properties": selected, "priority": priority,
                      "tenant": tenant, "deadline_s": deadline_s}
        with self._lock:
            self._seq += 1
            job_id = f"j-{self._seq:04d}"
            trace_path = os.path.join(self.data_dir,
                                      f"{job_id}.trace.jsonl")
            if resume_of is not None:
                # Claim the predecessor under the same lock that
                # allocates the id: a second resume of the same job
                # would put two live Supervisors on ONE checkpoint
                # rotation (interleaved writes rotate each other's
                # snapshots away) — first claim wins, later ones 409.
                if resume_of.resumed_by is not None:
                    raise JobConflict(
                        f"job {resume_of.id} was already resumed by "
                        f"{resume_of.resumed_by}")
                # Continue the predecessor's checkpoint generation:
                # the Supervisor resumes from its newest valid
                # snapshot, so the resubmission picks up exactly where
                # the preemption stopped.
                ckpt = resume_of.checkpoint_path
            else:
                ckpt = (os.path.join(self.data_dir,
                                     f"{job_id}.ckpt.npz")
                        if engine != "host" else None)
            job = Job(job_id, clean_spec, trace_path, ckpt)
            job.model = model
            job.program_key = self.registry.program_key(model_name,
                                                        params)
            if resume_of is not None:
                job.resume_of = resume_of.id
                resume_of.resumed_by = job_id
            job.tracer = RunTracer(trace_path, "service",
                                   meta={"job": job_id,
                                         "model": model_name})
            job.tracer.event("job_submit", job=job_id,
                             model=model_name, job_engine=engine,
                             _flush=True)
            self._jobs[job_id] = job
            self._order.append(job_id)
        try:
            self._queue.put(job_id, tenant=tenant, priority=priority)
        except JobQueueFull:
            # Admission rejected: roll the registration back so the
            # overflow leaves no phantom record (429 is retryable).
            with self._lock:
                self._jobs.pop(job_id, None)
                if job_id in self._order:
                    self._order.remove(job_id)
                if resume_of is not None:
                    resume_of.resumed_by = None
                tracer, job.tracer = job.tracer, None
            if tracer is not None:
                tracer.event("job_abort", job=job_id,
                             reason="queue_full", _flush=True)
                tracer.close()
            if self._control.armed:
                # Count + event the overflow as a shed and upgrade the
                # plain 429 with a drain-derived Retry-After.
                retry_after = self._control.note_queue_full(
                    tenant, priority, self._queue.qsize())
                raise JobShed("queue_full", retry_after) from None
            raise
        if self._control.armed and resume_of is None:
            self._control.note_admitted(job_id, tenant, priority,
                                        self._queue.qsize())
        return self.status(job_id)

    def _check_knobs(self, knobs) -> dict:
        out = {}
        for key, value in (knobs or {}).items():
            want = _KNOBS.get(key)
            if want is None:
                raise JobError(f"unknown engine knob {key!r}; "
                               f"accepts {sorted(_KNOBS)}")
            try:
                out[key] = bool(value) if want is bool else want(value)
            except (TypeError, ValueError) as e:
                raise JobError(f"knob {key!r}: {e}") from e
        return out

    # -- Execution ---------------------------------------------------------

    def _worker_loop(self) -> None:
        while True:
            popped = self._queue.pop()
            if popped is None:
                return
            job_id, tenant = popped
            try:
                job = self._jobs.get(job_id)
                if job is None:
                    continue
                with self._lock:
                    if job.state != "queued":
                        continue  # cancelled while queued
                    job.state = "running"
                    job.started_t = time.monotonic()
                try:
                    self._run_job(job)
                except Exception as e:  # noqa: BLE001 — the job record
                    # is the failure surface; the service must survive
                    self._finish(job, "failed",
                                 error=f"{type(e).__name__}: {e}"[:300])
            finally:
                # Quota release happens exactly once per pop, whatever
                # the job's fate — a leak here would starve the tenant.
                self._queue.task_done(tenant)

    def _factory(self, job: Job):
        engine = job.spec["engine"]
        knobs = dict(job.spec["knobs"])
        target = knobs.pop("target_state_count", None)

        def build(resume_from=None):
            builder = job.model.checker()
            if target:
                builder.target_state_count(target)
            if engine == "host":
                checker = builder.spawn_bfs()
            else:
                build_knobs = dict(knobs)
                if (self._control.armed
                        and "checkpoint_every_waves" in build_knobs):
                    # Brownout rung 2: widen the cadence for runs
                    # STARTED under pressure (cadence is sampled once
                    # per engine build; counters are cadence-
                    # independent, so bit-identity holds).
                    build_knobs["checkpoint_every_waves"] = \
                        self._control.ckpt_every(
                            build_knobs["checkpoint_every_waves"])
                checker = builder.spawn_tpu_bfs(
                    fused=(engine == "fused"),
                    checkpoint_path=job.checkpoint_path,
                    trace_path=job.trace_path,
                    program_cache=self.program_cache,
                    program_key=job.program_key,
                    resume_from=resume_from,
                    **build_knobs)
            with self._lock:
                job.checker = checker
                preempt_now = job.preempt_requested
            if preempt_now and hasattr(checker, "preempt"):
                # A DELETE raced the engine build: honor it at the
                # first wave boundary.
                checker.preempt()
            return checker

        return build

    def _mux_factory(self, job: Job, first_handle):
        """Supervisor factory for a mux tenant. Attempt 1 returns the
        pre-admitted handle; a retry (the group crashed, failing every
        tenant) re-admits into a fresh group resuming from the newest
        valid generation of THIS tenant's checkpoint — per-tenant
        counters survive the shared crash. No slot on the retry falls
        back to a supervised solo engine (the mux's bit-identity
        contract makes that a pure placement change)."""
        state = {"handle": first_handle}
        solo = self._factory(job)

        def build(resume_from=None):
            handle = state.pop("handle", None)
            if handle is None:
                handle = self._mux_admit_with(job, resume_from)
            if handle is None:
                return solo(resume_from=resume_from)
            with self._lock:
                job.checker = handle
                preempt_now = job.preempt_requested
            if preempt_now:
                # A DELETE raced the admission: honor it at the
                # group's next wave boundary.
                handle.preempt()
            return handle

        return build

    def _run_job(self, job: Job) -> None:
        if self._mux_eligible(job):
            handle = self._mux_admit(job)
            if handle is not None:
                # Round 17 (satellite): the mux path used to join the
                # handle directly, so a group crash (e.g. an injected
                # fault in a tenant checkpoint write) was terminal for
                # every tenant. Route it through the same Supervisor
                # the solo engines get.
                checker = Supervisor(
                    self._mux_factory(job, handle),
                    checkpoint_path=job.checkpoint_path,
                    trace_path=job.trace_path).run()
                self._finish(job, "preempted"
                             if getattr(checker, "preempted", False)
                             else "done")
                return
            # No slot / no valid resume image / group races: the solo
            # path below is always a correct fallback (bit-identical
            # results are the mux's contract, not a new semantics).
        factory = self._factory(job)
        if job.spec["engine"] == "host":
            checker = factory()
            checker.join()
        else:
            # Retry/abort events land in the JOB's trace stream, so a
            # job's whole supervised life lints as one file.
            checker = Supervisor(
                factory, checkpoint_path=job.checkpoint_path,
                trace_path=job.trace_path).run()
        if getattr(checker, "preempted", False):
            self._finish(job, "preempted")
        else:
            self._finish(job, "done")

    def _mux_eligible(self, job: Job) -> bool:
        """Multiplexing admission policy: classic engine only (the
        fused engine's device-resident loop declares itself
        ``_MUX_CAPABLE = False``), and only performance-schedule knobs
        — notably NOT ``target_state_count``, whose wave-granular early
        stop would make residual counts depend on who shared the wave
        (the solo-identity contract would silently break) — and no
        ``symmetric`` model: a group checks the raw space only."""
        if not self._mux or job.spec["engine"] != "classic":
            return False
        if getattr(job.model, "symmetric", False):
            return False
        try:
            from ..tpu.engine import TpuBfsChecker
            from .mux import MUX_KNOBS
        except ImportError:
            return False
        if not getattr(TpuBfsChecker, "_MUX_CAPABLE", False):
            return False
        return not (set(job.spec["knobs"]) - MUX_KNOBS)

    def _mux_admit(self, job: Job):
        """Admits the job into the open group for its corpus shape
        (creating one if needed); returns a TenantHandle or ``None``
        for the solo fallback. Shape key = cached canonical registry
        key + engine + exact knob set — the same safety condition the
        shared program cache uses, tightened to identical schedules."""
        resume_from = None
        if job.resume_of is not None:
            if job.checkpoint_path is None:
                return None
            resume_from = newest_valid_checkpoint(job.checkpoint_path)
            if resume_from is None:
                return None  # let the Supervisor surface the failure
        return self._mux_admit_with(job, resume_from)

    def _mux_admit_with(self, job: Job, resume_from: Optional[str]):
        """The group-lookup/admit loop with an explicit resume image
        (the Supervisor's retry path passes the newest valid generation
        of the tenant's own checkpoint)."""
        from .mux import MuxGroup

        key = (job.program_key, job.spec["engine"],
               tuple(sorted(job.spec["knobs"].items())))
        try:
            for _ in range(2):
                with self._mux_lock:
                    group = self._mux_groups.get(key)
                    if group is None or group.closed:
                        trace = os.path.join(
                            self.data_dir,
                            f"mux-{len(self._mux_all):03d}"
                            ".trace.jsonl")
                        group = MuxGroup(
                            job.model, knobs=job.spec["knobs"],
                            program_cache=self.program_cache,
                            program_key=job.program_key,
                            trace_path=trace,
                            max_jobs=self._mux_max_jobs,
                            control=self._control)
                        self._mux_groups[key] = group
                        self._mux_all.append(group)
                handle = group.admit(
                    job.id, trace_path=job.trace_path,
                    checkpoint_path=job.checkpoint_path,
                    resume_from=resume_from)
                if handle is not None:
                    return handle
                with self._mux_lock:
                    if (self._mux_groups.get(key) is group
                            and group.closed):
                        # Drained-and-closed between lookup and admit:
                        # retry once against a fresh group.
                        self._mux_groups.pop(key, None)
                        continue
                return None  # every slot busy — run solo
        except Exception:  # noqa: BLE001 — admission is an
            # optimization; any failure routes to the solo engine
            return None
        return None

    def _finish(self, job: Job, state: str,
                error: Optional[str] = None) -> None:
        checker = job.checker
        result: Dict = {}
        if checker is not None:
            try:
                result["states"] = checker.state_count()
                result["unique"] = checker.unique_state_count()
                if state == "done":
                    result["properties"] = self._verdicts(job, checker)
                stats_fn = getattr(checker, "scheduler_stats", None)
                result["jit_cache"] = (
                    stats_fn().get("program_cache")
                    if callable(stats_fn) else None)  # None: host engine
            except Exception as e:  # noqa: BLE001 — a torn engine must
                # not mask the job outcome
                result["result_error"] = f"{type(e).__name__}: {e}"[:200]
        with self._lock:
            job.state = state
            job.error = error
            job.finished_t = time.monotonic()
            job.result = result
            tracer = job.tracer
            job.tracer = None
        if self._obs.enabled and job.started_t is not None:
            # Job latency observations from the stamps the record
            # already carries; breach/snapshot events ride the job's
            # own trace stream while it is still open.
            self._obs.job(
                queue_s=job.started_t - job.submitted_t,
                run_s=job.finished_t - job.started_t,
                total_s=job.finished_t - job.submitted_t,
                ok=(state == "done"),
                engine=job.spec["engine"], tracer=tracer)
        if self._control.armed:
            self._control.note_done(ok=(state == "done"))
        if tracer is not None:
            if state == "done":
                tracer.event("job_done", job=job.id,
                             states=result.get("states", 0),
                             unique=result.get("unique", 0),
                             _flush=True)
            else:
                reason = state if error is None \
                    else f"{state}: {error}"
                tracer.event("job_abort", job=job.id, reason=reason,
                             _flush=True)
            tracer.close()

    def _verdicts(self, job: Job, checker) -> List[List]:
        """Explorer-style property rows, filtered to the submission's
        selection: ``[expectation, name, encoded_discovery|None]``."""
        from ..explorer import _EXPECTATION_NAMES

        selected = job.spec.get("properties")
        discoveries = checker.discoveries()
        rows = []
        for prop in job.model.properties():
            if selected is not None and prop.name not in selected:
                continue
            path = discoveries.get(prop.name)
            rows.append([_EXPECTATION_NAMES[prop.expectation], prop.name,
                        path.encode() if path is not None else None])
        return rows

    # -- Introspection / control ------------------------------------------

    def _job(self, job_id: str) -> Job:
        job = self._jobs.get(job_id)
        if job is None:
            raise KeyError(f"unknown job {job_id!r}")
        return job

    def status(self, job_id: str) -> dict:
        job = self._job(job_id)
        with self._lock:
            payload = {
                "id": job.id,
                "state": job.state,
                "model": job.spec["model"],
                "params": job.spec["params"],
                "engine": job.spec["engine"],
                "knobs": job.spec["knobs"],
                "priority": job.spec.get("priority", 0),
                "tenant": job.spec.get("tenant"),
                "deadline_s": job.spec.get("deadline_s"),
                "resume_of": job.resume_of,
                "error": job.error,
                "runtime_s": (round(job.runtime(), 3)
                              if job.started_t is not None else None),
                "checkpoint": job.checkpoint_path,
            }
            checker, result, state = job.checker, dict(job.result), \
                job.state
        if state == "running" and checker is not None:
            try:
                payload["states"] = checker.state_count()
                payload["unique"] = checker.unique_state_count()
            except Exception:  # noqa: BLE001 — a mid-teardown engine
                pass
        else:
            payload.update(result)
        return payload

    def jobs(self) -> List[dict]:
        with self._lock:
            order = list(self._order)
        return [self.status(job_id) for job_id in order]

    def trace_file(self, job_id: str) -> str:
        return self._job(job_id).trace_path

    def control_status(self) -> Optional[dict]:
        """The controller block ``/.healthz`` / ``/.ops`` embed;
        ``None`` when disarmed (probes see the pre-round-21 shape)."""
        return (self._control.status() if self._control.armed
                else None)

    def preempt(self, job_id: str) -> dict:
        """``DELETE /jobs/<id>``: stop the job at its next safe point,
        keeping the checkpoint for a later ``resume`` submission.
        Queued jobs are CANCELLED: removed from the queue outright and
        recorded as ``job_abort`` with reason ``cancelled`` (they never
        ran, so there is nothing to resume). Running host-engine jobs
        cannot be preempted (no checkpoint to resume — 409)."""
        job = self._job(job_id)
        tracer = checker = None
        cancelled = False
        with self._lock:
            state = job.state
            if state == "queued":
                job.state = "cancelled"
                job.finished_t = time.monotonic()
                tracer = job.tracer
                job.tracer = None
                cancelled = True
            elif state == "running":
                # Gate on the ENGINE, not the checker instance: a
                # DELETE racing the engine build (checker still None)
                # must 409 for a host job rather than return success
                # for a preempt the host engine can never honor.
                if job.spec["engine"] == "host":
                    raise JobConflict(
                        f"job {job_id} runs on the host engine, which "
                        "cannot preempt to a checkpoint")
                job.preempt_requested = True
                checker = job.checker
            # already-terminal: fall through to the status no-op
        if cancelled:
            self._queue.cancel(job_id)
        if tracer is not None:
            tracer.event("job_abort", job=job_id,
                         reason="cancelled" if cancelled
                         else "preempted", _flush=True)
            tracer.close()
        if checker is not None:
            checker.preempt()
        return self.status(job_id)

    def metrics_lines(self) -> List[str]:
        """The ``stpu_job_*`` Prometheus families for ``/.metrics``."""
        with self._lock:
            jobs = [self._jobs[j] for j in self._order]
            states: Dict[str, int] = {}
            for job in jobs:
                states[job.state] = states.get(job.state, 0) + 1
        # Jobs-by-state is a gauge (a job LEAVES "queued"/"running" —
        # the series decrease, which counter semantics forbid).
        lines = ["# TYPE stpu_jobs gauge"]
        lines += [f'stpu_jobs{{state="{s}"}} {c}'
                  for s, c in sorted(states.items())]
        lines += ["# TYPE stpu_job_queue_depth gauge",
                  f"stpu_job_queue_depth {self._queue.qsize()}"]
        cache = self.program_cache.stats()
        lines += [
            "# TYPE stpu_job_program_cache_hits_total counter",
            f"stpu_job_program_cache_hits_total {cache['hits']}",
            "# TYPE stpu_job_program_cache_misses_total counter",
            f"stpu_job_program_cache_misses_total {cache['misses']}",
            "# TYPE stpu_job_program_cache_programs gauge",
            f"stpu_job_program_cache_programs {cache['programs']}",
            # The cache's OWN counter families (round 16): the
            # stpu_job_* names above predate them and stay for
            # dashboard compatibility; these are the canonical ones,
            # including evictions.
            "# TYPE stpu_program_cache_hits_total counter",
            f"stpu_program_cache_hits_total {cache['hits']}",
            "# TYPE stpu_program_cache_misses_total counter",
            f"stpu_program_cache_misses_total {cache['misses']}",
            "# TYPE stpu_program_cache_evictions_total counter",
            f"stpu_program_cache_evictions_total {cache['evictions']}",
            "# TYPE stpu_program_cache_programs gauge",
            f"stpu_program_cache_programs {cache['programs']}",
        ]
        per_job: List[str] = []
        for job in jobs:
            status = self.status(job.id)
            if status.get("states") is not None:
                per_job.append((job.id, "states", status["states"]))
            if status.get("unique") is not None:
                per_job.append((job.id, "unique", status["unique"]))
            if status.get("runtime_s") is not None:
                per_job.append((job.id, "seconds",
                                status["runtime_s"]))
        for fam, mtype in (("states", "counter"), ("unique", "counter"),
                           ("seconds", "gauge")):
            rows = [(j, v) for j, f, v in per_job if f == fam]
            if not rows:
                continue
            # Round-18 naming audit: counters end in ``_total``; the
            # deprecated bare duals shipped one round and are gone.
            name = (f"stpu_job_{fam}_total" if mtype == "counter"
                    else f"stpu_job_{fam}")
            lines.append(f"# TYPE {name} {mtype}")
            lines += [f'{name}{{job="{j}"}} {v}' for j, v in rows]
        if self._control.armed:
            lines += self._control.metrics_lines()
        if self._obs.enabled and self._obs.hist is not None:
            # Live latency histograms (_bucket/_sum/_count) — same
            # emission helper trace_export uses offline.
            lines += prometheus_hist_lines(self._obs.hist.snapshot())
        slo = self._obs.slo_status()
        if slo is not None:
            from ..obs.slo import prometheus_slo_lines

            lines += prometheus_slo_lines(slo)
        return lines

    def close(self, preempt_running: bool = True) -> None:
        """Stops the worker pool. Running device jobs are preempted
        (their checkpoints stay resumable); queued jobs are dropped."""
        # Controller first: its tick thread calls back into submit/
        # preempt, and its shutdown terminally acknowledges parks
        # (the trace's park-pairing invariant) before workers drain.
        self._control.close()
        if preempt_running:
            with self._lock:
                jobs = list(self._jobs.values())
            for job in jobs:
                try:
                    self.preempt(job.id)
                except (JobConflict, KeyError):
                    pass
        self._queue.close()
        for t in self._workers:
            t.join(timeout=30)
        with self._mux_lock:
            groups = list(self._mux_all)
        for group in groups:
            group.close()
        # Close any still-open submit tracers (queued jobs dropped
        # without ever running).
        with self._lock:
            tracers = [j.tracer for j in self._jobs.values()
                       if j.tracer is not None]
            for j in self._jobs.values():
                j.tracer = None
        for tracer in tracers:
            tracer.close()
