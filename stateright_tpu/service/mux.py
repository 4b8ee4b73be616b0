"""Cross-job wave multiplexing: many concurrent checks, one device wave.

The round-14 job service runs each admitted job on its own engine
instance — correct, but wasteful at the service's target shape: many
concurrent SMALL jobs of the same corpus shape (same canonical
model/params/engine/knob cache key), each dispatching half-empty waves
that leave the device idle between host round-trips. The multiplexer
(round 16) admits same-shape jobs as *tenants* of one shared
``MuxGroup``: each group wave draws a batch from several tenants'
frontiers at once — packed rows carry a trailing tenant lane
(``tpu/packing.py``: ``PackedLayout.with_tenant_lane``) — and one
``build_mux_wave`` dispatch expands all of them, splitting the stats
vector per job via segment sums over that lane.

Isolation inside the shared visited table is by fingerprint tagging:
each tenant admission draws a unique 64-bit tag (splitmix-mixed
admission counter — NEVER reused, so a departing tenant's residual
entries can't falsely collide with a newcomer's states) and the wave
XORs dedup fingerprints with the owning tenant's tag before probing.
One open-addressing table therefore holds per-(job, state) entries and
tenants never dedup against each other; the added collision hazard is
the same 2^-64 class as the existing fingerprint/sentinel hazard.
Path fingerprints stay untagged, so parent maps, discoveries, and
checkpoints read real state fingerprints.

Bit-identity with solo runs is the load-bearing property (the
differential suite in ``tests/test_mux.py`` pins it): a tenant's rows
are assembled contiguously in its own queue order, the wave's
first-occurrence dedup and stable compaction preserve that order, and
cross-tenant fingerprints never collide — so each tenant's counts,
verdicts, discoveries, parents, and checkpoint bytes are exactly what
its solo engine would produce. The scope caveat is the same one the
cross-B parity suite carries: identity of the FULL surfaces holds for
runs that exhaust their frontier (or preempt-resume chains thereof);
an early-stopped run (``target_state_count``) stops at wave
granularity, so the service only multiplexes jobs without one.

Honesty notes (single-host scope):

- The group runs in ONE process against one device; this is service
  throughput for many small jobs, not distributed checking (the
  sharded/elastic engines own that axis).
- Tenant admission and table growth seed the device table through a
  host rebuild of the tagged fingerprint set (O(live states)) — cheap
  at the many-small-jobs target shape, and a wave-boundary operation,
  never per-wave.
- Mux jobs bypass the resilience ``Supervisor`` (a tenant failure
  fails that job; preempt/resume is the recovery story), and the
  multiplexer keeps the per-wave host boundary — no fused multi-wave
  device loop (``_MUX_CAPABLE`` is False on the fused engine).
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from typing import Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..checker.path import Path
from ..model import Expectation
from ..obs.hist import wave_obs_from_env
from ..obs.tracer import tracer_from_env
from .control import NULL_CONTROL
from ..tpu.engine import (batch_bucket_ladder, build_mux_wave,
                          host_table_insert, pick_bucket)
from ..tpu.hashing import SENTINEL, host_fp64
from ..tpu.packing import compile_layout

__all__ = ["MuxGroup", "TenantHandle", "MUX_KNOBS"]

#: Knobs a job may set and still be mux-eligible: pure performance
#: schedules shared by the whole group. Anything else (symmetry,
#: tiered-store budgets, ``target_state_count`` — whose early stop is
#: wave-granular and therefore composition-dependent) routes the job to
#: a solo engine.
MUX_KNOBS = frozenset({"batch_size", "max_batch_size", "table_capacity",
                       "checkpoint_every_waves", "async_io"})

_M64 = (1 << 64) - 1


def _splitmix64(x: int) -> int:
    """The tenant-tag mixer (splitmix64 finalizer): admission counter
    in, well-distributed 64-bit tag out."""
    x = (x + 0x9E3779B97F4A7C15) & _M64
    x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & _M64
    x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) & _M64
    return x ^ (x >> 31)


class _Tenant:
    """One admitted job's state inside a group. All mutable fields are
    guarded by the group's condition variable."""

    __slots__ = ("id", "slot", "tag", "ckpt_path", "tracer", "pending",
                 "parents", "parent_log", "parents_consumed",
                 "visited_blocks", "state_count", "unique_count",
                 "discoveries", "preempt_requested", "preempted",
                 "done", "error", "prog_hits", "prog_misses", "waves")

    def __init__(self, job_id: str, slot: int, tag: int,
                 ckpt_path: Optional[str], tracer):
        self.id = job_id
        self.slot = slot
        self.tag = tag
        self.ckpt_path = ckpt_path
        self.tracer = tracer
        self.pending: deque = deque()
        self.parents: Dict[int, Optional[int]] = {}
        self.parent_log: List = []
        self.parents_consumed = 0
        #: untagged dedup fingerprints, one block per producing wave
        #: (seed block first) — concatenated, this IS the tenant's
        #: visited set, which is how checkpoints and table rebuilds
        #: never need to untag a table scan.
        self.visited_blocks: List[np.ndarray] = []
        self.state_count = 0
        self.unique_count = 0
        self.discoveries: Dict[str, int] = {}
        self.preempt_requested = False
        self.preempted = False
        self.done = threading.Event()
        self.error: Optional[BaseException] = None
        self.prog_hits = 0
        self.prog_misses = 0
        self.waves = 0

    def rows_queued(self) -> int:
        return sum(len(b[1]) for b in self.pending)

    def rows_visited(self) -> int:
        return sum(len(b) for b in self.visited_blocks)


#: slot placeholder between reservation and seeded admission.
_RESERVED = object()


class TenantHandle:
    """The checker-shaped façade the job service holds for one tenant:
    the same count/discovery/preempt/join surface ``TpuBfsChecker``
    exposes, backed by the shared group."""

    def __init__(self, group: "MuxGroup", tenant: _Tenant):
        self._g = group
        self._t = tenant

    @property
    def preempted(self) -> bool:
        return self._t.preempted

    def state_count(self) -> int:
        with self._g._cv:
            return self._t.state_count

    def unique_state_count(self) -> int:
        with self._g._cv:
            return self._t.unique_count

    def discoveries(self) -> Dict[str, Path]:
        return self._g._tenant_discoveries(self._t)

    def preempt(self) -> None:
        with self._g._cv:
            self._t.preempt_requested = True
            self._g._cv.notify_all()

    def join(self) -> "TenantHandle":
        self._t.done.wait()
        if self._t.error is not None:
            raise self._t.error
        return self

    def is_done(self) -> bool:
        return self._t.done.is_set()

    def scheduler_stats(self) -> dict:
        g = self._g
        with g._cv:
            return {
                "engine": "mux",
                "jobs_in_group": len(g._live),
                "jobs_in_group_high_water": g._live_high_water,
                "group_waves": g._wave_count,
                "program_cache": {
                    "shared": g._prog_cache is not None,
                    "hits": self._t.prog_hits + g._prog_hits,
                    "misses": self._t.prog_misses + g._prog_misses,
                },
                "async_io": g._aio.stats(),
                "slo": g._wave_obs.slo_status(),
                "anomalies": g._wave_obs.anomalies(),
            }


class MuxGroup:
    """One shared engine multiplexing same-shape jobs' waves.

    The group owns a worker thread running the wave loop; tenants join
    at wave boundaries (``admit``), drain to their own checkpoint
    generation on preempt, and retire individually on completion
    without disturbing co-scheduled jobs. When the last tenant leaves
    the group closes itself (the service then builds a fresh group for
    the next same-shape arrival)."""

    def __init__(self, model, *, knobs: Optional[dict] = None,
                 program_cache=None, program_key: Optional[tuple] = None,
                 trace_path: Optional[str] = None, max_jobs: int = 8,
                 control=None):
        knobs = dict(knobs or {})
        bad = set(knobs) - MUX_KNOBS
        if bad:
            raise ValueError(f"knobs {sorted(bad)} are not mux-eligible")
        if getattr(model, "symmetric", False):
            raise ValueError("a symmetric model is not mux-eligible: a "
                             "group checks the raw space only")
        self._model = model
        dm = model.device_model()
        self._dm = dm
        self._properties = model.properties()
        if len(self._properties) > 32:
            raise NotImplementedError("at most 32 properties on device")
        device_props = dm.device_properties()
        self._prop_fns = [device_props.get(p.name)
                          for p in self._properties]
        self._ebits_all = 0
        self._eventually_idx: List[int] = []
        for i, p in enumerate(self._properties):
            if p.expectation is Expectation.EVENTUALLY:
                self._ebits_all |= 1 << i
                self._eventually_idx.append(i)

        self._B = max(1, int(knobs.get("batch_size", 1024)))
        self._buckets = batch_bucket_ladder(
            self._B, knobs.get("max_batch_size"))
        self._B_max = self._buckets[-1]
        self._F = dm.max_fanout
        self._W = dm.state_width
        lane_bits = getattr(dm, "lane_bits", lambda: None)()
        self._base = compile_layout(lane_bits, self._W)
        self._pack_on = (jax.default_backend() != "cpu"
                         and self._base.packs)
        #: storage width of a MODEL row (what solo engines store and
        #: what tenant checkpoints carry).
        self._Wrow = self._base.packed_width if self._pack_on else self._W
        #: the tenant-lane layout the wave program runs on; mux rows
        #: are one word wider (``packed[..., :-1]`` is exactly the solo
        #: storage row). With packing OFF the storage row is the raw
        #: register row, so the tenant lane derives from the IDENTITY
        #: layout — the model's bitfield plan must not leak into where
        #: the wave program finds the model part / tenant word.
        self._mux = (compile_layout(lane_bits, self._W) if self._pack_on
                     else compile_layout(None, self._W)
                     ).with_tenant_lane()
        self._Wmux = self._Wrow + 1
        self._ckpt_every = max(1, int(knobs.get(
            "checkpoint_every_waves", 64)))
        self._capacity = 1 << max(
            12, (int(knobs.get("table_capacity", 1 << 16)) - 1)
            .bit_length())

        self._J = max(1, int(max_jobs))
        self._prog_cache = program_cache if program_key is not None \
            else None
        self._prog_key = tuple(program_key) if program_key is not None \
            else None
        self._prog_hits = 0
        self._prog_misses = 0
        self._programs: dict = {}
        self._compile_dirty = False

        self._cv = threading.Condition()
        self._slots: List = [None] * self._J
        self._tags = np.zeros(self._J, np.uint64)
        self._tag_dev = jnp.asarray(self._tags)
        self._used_tags: set = set()
        self._live: List[_Tenant] = []
        self._joining: List[_Tenant] = []
        self._adm_seq = 0
        self._rr = 0
        self._ever = False
        self._stop = False
        self._closed = False
        self._live_rows = 0
        self._dead_rows = 0
        self._live_high_water = 0
        self._states_total = 0
        self._unique_total = 0
        self._wave_count = 0
        self._visited = None  # built by the first _rebuild_table
        # Round 17: background writer shared by tenant checkpoint
        # generations and the incremental visited-table folds. Knob-off
        # keeps the inline SyncWriter (submit == call, joins are
        # no-ops) — the pre-round-17 wave loop, unchanged.
        from ..io.async_io import writer_from_config

        self._aio = writer_from_config(knobs.get("async_io"),
                                       name="stpu-aio-mux")
        #: host mirror of the device table, kept current by per-wave
        #: background folds of each tenant's novel keys (async only) so
        #: a joiners-only boundary can skip the full host rebuild.
        self._shadow: Optional[np.ndarray] = None

        self._trace_path = trace_path
        self._tracer = tracer_from_env("mux", path=trace_path, meta={
            "model": type(model).__name__,
            "batch_size": self._B,
            "bucket_ladder": list(self._buckets),
            "table_capacity": self._capacity,
            "max_jobs": self._J,
            "state_width": self._W})
        #: service observability (obs/hist.py): group-wave latency
        #: histograms / SLO / anomaly attribution over the TOTAL line's
        #: entry (per-tenant latency belongs to the job service).
        self._wave_obs = wave_obs_from_env("mux")
        #: round-21 overload controller: armed, it adapts the per-wave
        #: batch budget from observed wave latency (and the brownout
        #: ladder) in `_wave`; disarmed NULL_CONTROL keeps the fixed
        #: B_max cap — the pre-round-21 allocation, unchanged.
        self._control = control if control is not None else NULL_CONTROL
        self._wave_t0: Optional[float] = None

        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    # -- Admission ---------------------------------------------------------

    def admit(self, job_id: str, *, trace_path: Optional[str] = None,
              checkpoint_path: Optional[str] = None,
              resume_from: Optional[str] = None
              ) -> Optional[TenantHandle]:
        """Admits one job as a tenant; returns its handle, or ``None``
        when the group cannot take it (every slot busy, or the group
        already closed) — the service then opens a fresh group. Host
        seeding (init-state encode or checkpoint load) runs outside the
        group lock; the wave loop integrates the tenant's fingerprints
        into the shared table at its next wave boundary."""
        with self._cv:
            if self._closed or self._stop:
                return None
            free = [s for s in range(self._J) if self._slots[s] is None]
            if not free:
                return None
            slot = free[0]
            self._slots[slot] = _RESERVED
            self._adm_seq += 1
            tag = _splitmix64(self._adm_seq)
            while tag in self._used_tags or tag == 0:
                self._adm_seq += 1
                tag = _splitmix64(self._adm_seq)
            self._used_tags.add(tag)
        try:
            tenant = self._build_tenant(job_id, slot, tag, trace_path,
                                        checkpoint_path, resume_from)
        except BaseException:
            with self._cv:
                self._slots[slot] = None
            raise
        # Per-admission shared-program resolution: the group builds the
        # wave program once, but EVERY admission resolves it through
        # the process-wide cache so the Nth same-shape job records a
        # genuine hit — the same amortization signal a solo engine's
        # scheduler_stats carries.
        self._admission_program(tenant)
        with self._cv:
            if self._closed or self._stop:
                # The group drained and closed while we seeded; the
                # caller opens a fresh group.
                self._slots[slot] = None
                tenant.tracer.close()
                return None
            self._slots[slot] = tenant
            self._tags[slot] = np.uint64(tag)
            self._joining.append(tenant)
            self._cv.notify_all()
        return TenantHandle(self, tenant)

    def _build_tenant(self, job_id, slot, tag, trace_path, ckpt_path,
                      resume_from) -> _Tenant:
        tracer = tracer_from_env("mux", path=trace_path, meta={
            "model": type(self._model).__name__, "job": job_id,
            "mux_slot": slot})
        t = _Tenant(job_id, slot, tag, ckpt_path, tracer)
        if resume_from is not None:
            self._load_tenant_checkpoint(t, resume_from)
            return t
        model, dm = self._model, self._dm
        init_states = [s for s in model.init_states()
                       if model.within_boundary(s)]
        t.state_count = len(init_states)
        seen: set = set()
        vecs: List[np.ndarray] = []
        fps: List[int] = []
        for s in init_states:
            vec = np.asarray(dm.encode(s), np.uint32)
            fp = host_fp64(vec)
            if fp in seen:
                continue
            seen.add(fp)
            vecs.append(vec)
            fps.append(fp)
        fps_arr = np.array(fps, np.uint64)
        if vecs:
            seed = np.stack(vecs).astype(np.uint32)
            t.pending.append((
                self._rows_with_tag(seed, slot), fps_arr,
                np.full(len(fps), self._ebits_all, np.uint32)))
        t.unique_count = len(fps)
        t.parent_log = [(fps_arr, None)]
        t.visited_blocks = [fps_arr]
        return t

    def _load_tenant_checkpoint(self, t: _Tenant, path: str) -> None:
        """Mirror of the solo engine's ``_load_checkpoint``: restores
        counts/discoveries/pending/parents and the visited set from a
        (solo- or mux-written — they are byte-identical) snapshot."""
        from ..checkpoint_format import (load_checkpoint, pending_rows,
                                         validate_header)

        with load_checkpoint(path) as data:
            header = validate_header(
                data, model_name=type(self._model).__name__,
                state_width=self._W, use_symmetry=False)
            t.state_count = int(header["state_count"])
            t.unique_count = int(header["unique_count"])
            t.discoveries = {k: int(v) for k, v
                             in header["discoveries"].items()}
            vecs = pending_rows(data, header, self._W)
            if self._pack_on:
                self._base.check_fits(vecs)
            fps = np.asarray(data["pending_fps"], np.uint64)
            ebits = np.asarray(data["pending_ebits"], np.uint32)
            if len(fps):
                t.pending.append((self._rows_with_tag(vecs, t.slot),
                                  fps, ebits))
            t.parents = {
                int(c): (None if r else int(p))
                for c, p, r in zip(data["parent_child"].tolist(),
                                   data["parent_parent"].tolist(),
                                   data["parent_rooted"].tolist())}
            visited = np.asarray(data["visited"], np.uint64)
            refs = header.get("store")
            if refs:
                # A snapshot of a tiered-store run: materialize the
                # cold segments (the mux has no store; slower, never
                # wrong — the solo engine's no-store branch).
                from ..store.tiered import load_cold_refs

                cold = load_cold_refs(refs, base_dir=os.path.dirname(
                    os.path.abspath(path)))
                if len(cold):
                    visited = np.concatenate([visited, cold])
            t.visited_blocks = [visited]

    def _rows_with_tag(self, model_rows: np.ndarray,
                       slot: int) -> np.ndarray:
        """UNPACKED model rows -> storage rows with the tenant word."""
        model_rows = np.asarray(model_rows, np.uint32)
        tags = np.full(len(model_rows), slot, np.uint32)
        if self._pack_on:
            self._base.check_fits(model_rows)
            return self._mux.pack_tenant_np(model_rows, tags)
        return np.concatenate([model_rows, tags[:, None]], axis=1)

    # -- Shared wave program ----------------------------------------------

    def _shared_key(self, bucket: int) -> tuple:
        return (self._prog_key, "mux", self._pack_on, False, self._J,
                bucket, self._capacity)

    def _build_program(self, bucket: int):
        return build_mux_wave(self._dm, bucket, self._capacity,
                              self._prop_fns, False, max_jobs=self._J,
                              layout=self._mux, pack_on=self._pack_on)

    def _admission_program(self, tenant: _Tenant) -> None:
        if self._prog_cache is None:
            return
        _, hit = self._prog_cache.get_or_build(
            self._shared_key(self._B), lambda: self._build_program(
                self._B))
        if hit:
            tenant.prog_hits += 1
        else:
            tenant.prog_misses += 1

    def _program(self, bucket: int):
        key = (bucket, self._capacity)
        prog = self._programs.get(key)
        if prog is not None:
            return prog
        if self._prog_cache is not None:
            prog, hit = self._prog_cache.get_or_build(
                self._shared_key(bucket),
                lambda: self._build_program(bucket))
            if hit:
                self._prog_hits += 1
            else:
                self._prog_misses += 1
                self._compile_dirty = True
        else:
            prog = self._build_program(bucket)
            self._compile_dirty = True
        self._programs[key] = prog
        return prog

    # -- Shared visited table ---------------------------------------------

    def _rebuild_table(self) -> None:
        """Rebuilds the device table from the LIVE tenants' tagged
        fingerprint sets (dropping any dead tenants' residual entries),
        growing capacity first if needed. A wave-boundary host
        operation — admission, growth, and dead-entry compaction all
        land here."""
        while self._capacity // 2 < (self._live_rows
                                     + 2 * self._B_max * self._F):
            self._capacity *= 2
        table = np.full(self._capacity, SENTINEL, np.uint64)
        for t in self._live:
            if t.visited_blocks:
                fps = np.concatenate(
                    [np.asarray(b, np.uint64)
                     for b in t.visited_blocks])
                host_table_insert(table, fps ^ np.uint64(t.tag))
        self._visited = jax.device_put(jnp.asarray(table))
        # The freshly built table IS the new shadow (device holds its
        # own copy; later in-place folds never touch device memory).
        self._shadow = table if self._aio.enabled else None
        self._dead_rows = 0

    def _integrate_joiners(self, joiners) -> None:
        """Folds joiners into the shared table at a wave boundary.

        Knob off this is the full host rebuild. Knob on, the per-wave
        background folds have kept ``_shadow`` membership-identical to
        the device table, so a clean boundary (no dead entries to
        compact, no growth needed) only inserts the joiners' rows and
        re-uploads — the incremental path. Probe placement can differ
        from a full rebuild; membership (the only thing lookups see)
        cannot, and dead entries force the full path exactly where the
        sync rebuild would have dropped them."""
        if (not self._aio.enabled or self._shadow is None
                or self._dead_rows
                or self._capacity // 2 < (self._live_rows
                                          + 2 * self._B_max * self._F)):
            self._rebuild_table()
            return
        self._aio.join()  # pending folds land before the upload
        for t in joiners:
            if t.visited_blocks:
                fps = np.concatenate([np.asarray(b, np.uint64)
                                      for b in t.visited_blocks])
                host_table_insert(self._shadow, fps ^ np.uint64(t.tag))
        self._visited = jax.device_put(jnp.asarray(self._shadow))

    def _table_stale(self) -> bool:
        occupied = self._live_rows + self._dead_rows
        return (self._visited is None
                or occupied + 2 * self._B_max * self._F
                > self._capacity // 2
                or self._dead_rows > max(self._live_rows, 4096))

    # -- Wave loop ---------------------------------------------------------

    def _run(self) -> None:
        try:
            while True:
                with self._cv:
                    while not (self._joining or self._live
                               or self._stop or self._ever):
                        self._cv.wait(timeout=0.5)
                    if not self._joining and not self._live:
                        # Drained (or stopped before first admission):
                        # the group is done for good.
                        self._closed = True
                        return
                    joiners, self._joining = self._joining, []
                    if joiners:
                        self._ever = True
                        self._live.extend(joiners)
                        for t in joiners:
                            self._live_rows += t.rows_visited()
                        self._live_high_water = max(
                            self._live_high_water, len(self._live))
                        self._tag_dev = jnp.asarray(self._tags)
                    if self._stop:
                        for t in self._live:
                            t.preempt_requested = True
                if joiners:
                    self._integrate_joiners(joiners)
                # Wave boundary: retire finished tenants first (a run
                # that drained naturally completed — mirror of the solo
                # loop exiting before it rechecks the preempt flag),
                # then preempted ones (each drains to its own
                # checkpoint generation without touching the others).
                for t in list(self._live):
                    if (not t.pending
                            or len(t.discoveries)
                            == len(self._properties)):
                        self._retire(t, preempted=False)
                for t in list(self._live):
                    if t.preempt_requested:
                        self._retire(t, preempted=True)
                if not self._live:
                    continue
                if self._wave_count % self._ckpt_every == 0 \
                        and self._wave_count:
                    # Safe point: join any still-pending generation
                    # before starting a new one — per-tenant rotation
                    # order holds, and a writer-thread fault from the
                    # previous cycle surfaces HERE (group failure, the
                    # Supervisor-visible crash, same as the sync path).
                    self._aio.join()
                    for t in self._live:
                        if t.ckpt_path is not None:
                            self._write_tenant_checkpoint(t)
                if self._table_stale():
                    old = self._capacity
                    self._rebuild_table()
                    if self._tracer.enabled and self._capacity != old:
                        self._tracer.event("grow", kind="table",
                                           old=old, new=self._capacity)
                self._wave()
        except BaseException as e:  # surfaced at every tenant's join()
            with self._cv:
                self._closed = True
                pending = self._live + self._joining
                self._live, self._joining = [], []
                for t in pending:
                    if t.error is None:
                        t.error = e
            for t in pending:
                t.tracer.close()
                t.done.set()
        finally:
            with self._cv:
                self._closed = True
            self._aio.close()  # drains; never raises
            if self._wave_obs.enabled:
                self._wave_obs.close(self._tracer)
            self._tracer.close()

    def _wave(self) -> None:
        self._wave_t0 = time.monotonic()
        with self._cv:
            order = (self._live[self._rr % len(self._live):]
                     + self._live[:self._rr % len(self._live)])
            self._rr += 1
            queued = [t.rows_queued() for t in order]
        # Adaptive sizing (round 21): an armed controller caps the
        # wave budget from observed per-wave latency for this program
        # key (stepping down the bucket ladder while p90 exceeds the
        # target, plus one rung under brownout), never below one row
        # per live tenant — the fairness floor survives adaptation.
        # Tenant rows are still assembled contiguously in queue order,
        # so the split stays allocation-independent and bit-identity
        # with solo runs holds at ANY budget.
        cap = (self._control.mux_budget(self._prog_key, self._buckets,
                                        len(order))
               if self._control.armed else self._B_max)
        budget = min(sum(queued), cap)
        # Fair allocation with contiguous per-tenant segments: equal
        # shares first (rotated start, so no tenant owns the front of
        # the batch), then leftover capacity to whoever still has rows.
        share = budget // len(order)
        alloc = [min(q, share) for q in queued]
        left = budget - sum(alloc)
        for i, q in enumerate(queued):
            if left <= 0:
                break
            extra = min(q - alloc[i], left)
            alloc[i] += extra
            left -= extra
        bucket = pick_bucket(self._buckets, budget)
        batch_vecs = np.zeros((bucket, self._Wmux), np.uint32)
        batch_fps = np.zeros(bucket, np.uint64)
        batch_ebits = np.zeros(bucket, np.uint32)
        segments: List[tuple] = []
        row = 0
        for t, take in zip(order, alloc):
            if not take:
                continue
            lo = row
            taken = 0
            while t.pending and taken < take:
                vecs, fps, ebits = t.pending[0]
                k = len(fps)
                use = min(k, take - taken)
                if use == k:
                    t.pending.popleft()
                else:
                    t.pending[0] = (vecs[use:], fps[use:], ebits[use:])
                    vecs, fps, ebits = (vecs[:use], fps[:use],
                                        ebits[:use])
                batch_vecs[row:row + use] = vecs
                batch_fps[row:row + use] = fps
                batch_ebits[row:row + use] = ebits
                row += use
                taken += use
            segments.append((t, lo, row))
        n = row
        valid = np.arange(bucket) < n
        outs = self._program(bucket)(
            jnp.asarray(batch_vecs), jnp.asarray(valid), self._tag_dev,
            self._visited)
        (conds_out, terminal, seg_succ, seg_cand, seg_novel, new_count,
         new_vecs, new_fps, new_dedup, new_parent,
         self._visited) = outs
        self._process(segments, bucket, n, batch_vecs, batch_fps,
                      batch_ebits, valid, conds_out, terminal,
                      seg_succ, seg_cand, seg_novel, new_count,
                      new_vecs, new_fps, new_dedup, new_parent)

    def _host_conds(self, conds_out, batch_vecs, n) -> List[np.ndarray]:
        """Mirror of the solo engine's ``_eval_host_conds`` over mux
        rows (the tenant word is stripped before decode)."""
        model = self._model
        conds: List[np.ndarray] = []
        it = iter(conds_out)
        decoded: Optional[list] = None
        for i, fn in enumerate(self._prop_fns):
            if fn is not None:
                conds.append(np.asarray(next(it)))
                continue
            if decoded is None:
                decode = self._dm.decode
                rows = batch_vecs[:, :-1]
                unpacked = (self._base.unpack_np(rows) if self._pack_on
                            else rows)
                decoded = [(r, decode(unpacked[r])) for r in range(n)]
            cond = np.zeros(len(batch_vecs), bool)
            prop_cond = self._properties[i].condition
            for r, state in decoded:
                cond[r] = bool(prop_cond(model, state))
            conds.append(cond)
        return conds

    def _check_error_lane(self, new_vecs: np.ndarray) -> None:
        lane = self._dm.error_lane
        if lane is None or not new_vecs.size:
            return
        rows = new_vecs[:, :-1]
        col = (self._base.lane_np(rows, lane) if self._pack_on
               else rows[:, lane])
        if col.any():
            raise RuntimeError(
                f"device model error lane {lane} is set in a generated "
                "state: an encoding capacity was exceeded (for actor "
                "models: raise net_slots)")

    def _process(self, segments, bucket, n, batch_vecs, batch_fps,
                 batch_ebits, valid, conds_out, terminal, seg_succ,
                 seg_cand, seg_novel, new_count, new_vecs, new_fps,
                 new_dedup, new_parent) -> None:
        properties = self._properties
        conds = self._host_conds(conds_out, batch_vecs, n)
        terminal = np.asarray(terminal)
        k = int(new_count)
        new_vecs = np.asarray(new_vecs)[:k]
        new_fps = np.asarray(new_fps)[:k]
        new_dedup = np.asarray(new_dedup)[:k]
        parent_rows = np.asarray(new_parent)[:k]
        seg_succ = np.asarray(seg_succ)
        seg_cand = np.asarray(seg_cand)
        seg_novel = np.asarray(seg_novel)
        ebits_after = batch_ebits.copy()
        for i in self._eventually_idx:
            ebits_after &= ~np.where(conds[i], np.uint32(1 << i),
                                     np.uint32(0))
        jobs_in_wave = len(segments)
        succ_total = cand_total = 0
        per_job: List[tuple] = []
        for t, lo, hi in segments:
            sel = (parent_rows >= lo) & (parent_rows < hi)
            t_k = int(sel.sum())
            t_succ = int(seg_succ[t.slot])
            t_cand = int(seg_cand[t.slot])
            if t_k != int(seg_novel[t.slot]):
                raise RuntimeError(
                    f"mux wave split inconsistency: segment of job "
                    f"{t.id} claims {int(seg_novel[t.slot])} novel "
                    f"rows, parent ranges yield {t_k}")
            succ_total += t_succ
            cand_total += t_cand
            failure: Optional[BaseException] = None
            try:
                self._check_error_lane(new_vecs[sel])
            except RuntimeError as e:
                failure = e
            with self._cv:
                t.state_count += t_succ
                # ALWAYS/SOMETIMES discoveries: first hit in the
                # tenant's queue order (its rows are contiguous and
                # ordered, so "first row in the segment" IS the solo
                # rule).
                for i, prop in enumerate(properties):
                    if prop.name in t.discoveries:
                        continue
                    if prop.expectation is Expectation.ALWAYS:
                        hits = valid[lo:hi] & ~conds[i][lo:hi]
                    elif prop.expectation is Expectation.SOMETIMES:
                        hits = valid[lo:hi] & conds[i][lo:hi]
                    else:
                        continue
                    rows = np.flatnonzero(hits)
                    if rows.size:
                        t.discoveries[prop.name] = int(
                            batch_fps[lo + rows[0]])
                for r in np.flatnonzero(terminal[lo:hi]
                                        & (ebits_after[lo:hi] != 0)):
                    for i in self._eventually_idx:
                        prop = properties[i]
                        if (ebits_after[lo + r] >> i) & 1 \
                                and prop.name not in t.discoveries:
                            t.discoveries[prop.name] = int(
                                batch_fps[lo + r])
                if t_k and failure is None:
                    t.parent_log.append(
                        (new_fps[sel], batch_fps[parent_rows[sel]]))
                    t.unique_count += t_k
                    t.pending.append((new_vecs[sel], new_fps[sel],
                                      ebits_after[parent_rows[sel]]))
                    t.visited_blocks.append(new_dedup[sel])
                    self._live_rows += t_k
                elif t_k:
                    # The failed tenant's insertions stay in the table
                    # as dead entries until the next rebuild.
                    self._dead_rows += t_k
                if failure is not None:
                    t.error = failure
                t.waves += 1
            if t_k and failure is None and self._shadow is not None:
                # Background fold: mirror the device table's in-place
                # insertions into the host shadow. The shadow array is
                # captured at submit time — a full rebuild may swap it
                # mid-flight, in which case the fold lands on the
                # retired array (harmless: the rebuild re-inserted
                # these keys from visited_blocks).
                shadow = self._shadow
                keys = new_dedup[sel] ^ np.uint64(t.tag)
                self._aio.submit(
                    lambda shadow=shadow, keys=keys:
                        host_table_insert(shadow, keys),
                    kind="fold")
            per_job.append((t, hi - lo, t_succ, t_cand, t_k))
        with self._cv:
            self._states_total += succ_total
            self._unique_total += k
            self._wave_count += 1
            states, unique = self._states_total, self._unique_total
        for t, _, _, _, _ in per_job:
            if t.error is not None and not t.done.is_set():
                self._retire_failed(t)
        compiled = self._compile_dirty
        self._compile_dirty = False
        total_entry = None
        if self._tracer.enabled or self._wave_obs.enabled:
            total_entry = self._wave_entry(
                states, unique, bucket, n, succ_total, cand_total, k,
                compiled, None, jobs_in_wave)
        if self._tracer.enabled:
            # One TOTAL line (job_id null, jobs_in_wave = J) followed
            # by exactly J attributed lines whose deltas sum to it —
            # the v9 split trace_lint enforces. Every line carries the
            # GROUP-cumulative states/unique (the lint's per-run
            # monotone counters); tenant cumulatives live in the
            # per-job trace files under their own run ids.
            self._tracer.wave(total_entry)
            for t, t_rows, t_succ, t_cand, t_k in per_job:
                self._tracer.wave(self._wave_entry(
                    states, unique, bucket, t_rows, t_succ, t_cand,
                    t_k, False, t.id, jobs_in_wave))
        if self._wave_obs.enabled:
            # Group-wave latency over the TOTAL line (entries carry no
            # "t" — the facade stamps its own monotonic clock).
            self._wave_obs.wave(total_entry, self._tracer)
        for t, t_rows, t_succ, t_cand, t_k in per_job:
            if t.tracer.enabled:
                with self._cv:
                    t_states, t_unique = t.state_count, t.unique_count
                t.tracer.wave(self._wave_entry(
                    t_states, t_unique, bucket, t_rows, t_succ, t_cand,
                    t_k, compiled, t.id, jobs_in_wave))
        if self._control.armed and self._wave_t0 is not None:
            # Feed the adaptive-budget histogram (compile waves are
            # excluded inside — a lazy XLA build is not a latency
            # regression).
            self._control.note_wave(
                self._prog_key, time.monotonic() - self._wave_t0,
                compiled=compiled)

    def _wave_entry(self, states, unique, bucket, rows, succ, cand,
                    novel, compiled, job_id, jobs_in_wave) -> dict:
        occupied = self._live_rows + self._dead_rows
        return {
            "states": int(states), "unique": int(unique),
            "bucket": int(bucket), "waves": 1, "inflight": 0,
            "compiled": bool(compiled), "successors": int(succ),
            "candidates": int(cand), "novel": int(novel),
            "out_rows": int(bucket * self._F),
            "capacity": int(self._capacity),
            "load_factor": round(occupied / self._capacity, 4),
            "overflow": False, "bytes_per_state": 4 * self._Wmux,
            "arena_bytes": None, "table_bytes": self._capacity * 8,
            "kernel_path": "xla", "rows": int(rows),
            "job_id": job_id, "jobs_in_wave": int(jobs_in_wave),
        }

    # -- Retirement / checkpoints ------------------------------------------

    def _retire(self, t: _Tenant, preempted: bool) -> None:
        # Surface any pending writer fault from OTHER tenants' periodic
        # generations BEFORE this tenant's final one: a deferred group
        # failure must stay a group failure (the Supervisor-visible
        # crash), not be swallowed as this tenant's own checkpoint
        # error. Raises into _run's handler, exactly like the sync
        # path's inline fault.
        self._aio.join()
        try:
            if t.ckpt_path is not None:
                self._write_tenant_checkpoint(t)
                # The final generation must be durable before done is
                # set — the client reads the file right after join().
                self._aio.join()
        except BaseException as e:  # noqa: BLE001 — fail THIS tenant
            t.error = e
        with self._cv:
            t.preempted = preempted and t.error is None
            self._live.remove(t)
            self._slots[t.slot] = None
            rows = t.rows_visited()
            self._live_rows -= rows
            self._dead_rows += rows
        t.tracer.close()
        t.done.set()

    def _retire_failed(self, t: _Tenant) -> None:
        with self._cv:
            if t in self._live:
                self._live.remove(t)
                self._slots[t.slot] = None
                rows = t.rows_visited()
                self._live_rows -= rows
                self._dead_rows += rows
        t.tracer.close()
        t.done.set()

    def _write_tenant_checkpoint(self, t: _Tenant) -> None:
        from ..checkpoint_format import write_atomic

        # Snapshot capture is synchronous (bit-identical content either
        # knob); only CRC/serialize/rename rides the writer. FIFO + the
        # safe-point joins preserve per-tenant generation order.
        payload = self._tenant_snapshot(t)
        path = t.ckpt_path
        self._aio.submit(lambda: write_atomic(path, payload),
                         kind="checkpoint")

    def _tenant_snapshot(self, t: _Tenant) -> dict:
        """Mirror of the solo engine's ``_snapshot`` for ONE tenant —
        same header fields, same canonical (sorted) visited order, and
        pending rows with the tenant word stripped, so the bytes match
        a solo run of the same job section for section."""
        from ..checkpoint_format import make_header

        parents = self._tenant_parent_map(t)
        n = len(parents)
        child = np.fromiter(parents.keys(), np.uint64, n)
        parent = np.fromiter((0 if v is None else v
                              for v in parents.values()), np.uint64, n)
        rooted = np.fromiter((v is None for v in parents.values()),
                             bool, n)
        with self._cv:
            blocks = list(t.pending)
            visited_blocks = list(t.visited_blocks)
            state_count, unique_count = t.state_count, t.unique_count
            discoveries = dict(t.discoveries)
        if blocks:
            vecs = np.concatenate([b[0][:, :-1] for b in blocks])
            fps = np.concatenate([b[1] for b in blocks])
            ebits = np.concatenate([b[2] for b in blocks])
        else:
            vecs = np.zeros((0, self._Wrow), np.uint32)
            fps = np.zeros(0, np.uint64)
            ebits = np.zeros(0, np.uint32)
        visited = (np.concatenate([np.asarray(b, np.uint64)
                                   for b in visited_blocks])
                   if visited_blocks else np.zeros(0, np.uint64))
        visited = np.sort(visited)
        header = make_header(
            model_name=type(self._model).__name__,
            state_width=self._W, state_count=state_count,
            unique_count=unique_count, use_symmetry=False,
            discoveries=discoveries,
            row_format="packed" if self._pack_on else "u32",
            lane_bits=self._base.specs if self._pack_on else None,
            packed_width=self._Wrow if self._pack_on else None,
            store=None)
        return dict(header=header, visited=visited, pending_vecs=vecs,
                    pending_fps=fps, pending_ebits=ebits,
                    parent_child=child, parent_parent=parent,
                    parent_rooted=rooted)

    # -- Paths / discoveries -----------------------------------------------

    def _tenant_parent_map(self, t: _Tenant) -> Dict[int, Optional[int]]:
        with self._cv:
            log = t.parent_log
            while t.parents_consumed < len(log):
                child_fps, parent_fps = log[t.parents_consumed]
                if parent_fps is None:
                    for f in child_fps:
                        t.parents.setdefault(int(f), None)
                else:
                    for f, p in zip(child_fps.tolist(),
                                    parent_fps.tolist()):
                        t.parents.setdefault(f, p)
                log[t.parents_consumed] = None
                t.parents_consumed += 1
        return t.parents

    def _fingerprint_state(self, state) -> int:
        return host_fp64(np.asarray(self._dm.encode(state), np.uint32))

    def _tenant_discoveries(self, t: _Tenant) -> Dict[str, Path]:
        with self._cv:
            found = list(t.discoveries.items())
        parents = self._tenant_parent_map(t)
        out: Dict[str, Path] = {}
        for name, fp in found:
            fingerprints: deque = deque()
            next_fp = fp
            while next_fp in parents:
                source = parents[next_fp]
                fingerprints.appendleft(next_fp)
                if source is None:
                    break
                next_fp = source
            out[name] = Path.from_fingerprints(
                self._model, fingerprints,
                fingerprint_fn=self._fingerprint_state)
        return out

    # -- Lifecycle ---------------------------------------------------------

    @property
    def closed(self) -> bool:
        with self._cv:
            return self._closed

    def jobs_in_group(self) -> int:
        with self._cv:
            return len(self._live) + len(self._joining)

    def close(self, timeout: float = 30.0) -> None:
        """Stops the group: live tenants preempt (draining to their
        checkpoints), then the loop exits. Idempotent."""
        with self._cv:
            self._stop = True
            for t in self._live:
                t.preempt_requested = True
            self._cv.notify_all()
        self._thread.join(timeout=timeout)

    def join(self, timeout: Optional[float] = None) -> None:
        self._thread.join(timeout=timeout)
