"""``TpuBfsChecker``: breadth-first model checking as device frontier waves.

The TPU-native inversion of the reference's `src/checker/bfs.rs`: instead
of worker threads pulling one state at a time through virtual dispatch
(`bfs.rs:75-152`), each *wave* advances the whole frontier as one jitted
XLA program:

1. vmapped property predicates over the frontier batch (`bfs.rs:192-226`),
2. vmapped successor generation (``DeviceModel.step``) with a static
   max-fanout and validity mask (`bfs.rs:231-244`),
3. device fingerprinting of every successor (`lib.rs:307-311`),
4. dedup: intra-wave first-occurrence via a sort over the (small) wave
   array, cross-wave membership + insertion via an HBM-resident
   open-addressing ``uint64`` hash table (the analog of the amortized-O(1)
   ``DashMap`` visited set, `bfs.rs:26,245-259`): a ``lax.while_loop`` of
   gather / claim-scatter / re-gather rounds resolves every candidate in
   O(probe-chain) steps, so per-wave cost is independent of table
   occupancy (no re-sorting of the resident set),
5. frontier compaction via a stable argsort so surviving successors keep
   host-BFS enqueue order (this preserves the reference's level order and
   therefore its exact discovery traces).

The host keeps the parent-pointer map (fingerprint -> parent fingerprint,
`bfs.rs:26`) fed by a per-wave stream of new states, so discovery paths are
reconstructed by model replay exactly as the reference does
(`bfs.rs:314-342`) — using the *device* fingerprint function.

Eventually-property bits ride along as a per-row ``uint32`` bitmask
(`EventuallyBits`, `checker.rs:340-347`), cleared on device-evaluated
satisfaction and converted to counterexamples at terminal states
(`bfs.rs:265-272`), preserving the reference's documented revisit caveats
(`bfs.rs:239-259`).
"""

from __future__ import annotations

import hashlib
import os
import threading
import time
import warnings
from collections import deque
from typing import Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..checker.base import Checker
from ..checker.path import Path
from ..checker.visitor import as_visitor
from ..jit_cache import enable_persistent_jit_cache
from ..model import Expectation, Model
from ..obs import (prof_from_env, recorder_from_env, tracer_from_env,
                   wave_obs_from_env)
from ..resilience.faults import fault_plan_from_env, is_oom
from ..store.tiered import FrontierRef, store_from_config
from .device_model import DeviceModel
from .hashing import SENTINEL, device_fp64, host_fp64

__all__ = ["TpuBfsChecker", "build_wave", "build_mux_wave",
           "build_regather", "batch_bucket_ladder", "pick_bucket",
           "succ_bucket_ladder"]


def batch_bucket_ladder(base: int, max_batch: Optional[int]) -> tuple:
    """The adaptive scheduler's dispatch widths: ``base`` followed by
    doublings up to ``max_batch`` (inclusive, rounded up to the next
    power of two). With ``max_batch`` unset the ladder is the single
    rung ``(base,)`` — the fixed-width behavior, zero extra compiles.

    Wave results are independent of the dispatch width (the
    first-occurrence dedup rule preserves global queue order whatever
    the wave composition — see the cross-B parity suite), so the ladder
    is purely a performance schedule: each rung costs one compile of
    the wave/dispatch program, amortized across every dispatch at that
    width.
    """
    base = max(1, int(base))
    if not max_batch or int(max_batch) <= base:
        return (base,)
    top = 1 << max(0, int(max_batch) - 1).bit_length()
    ladder = [base]
    while ladder[-1] * 2 <= top:
        ladder.append(ladder[-1] * 2)
    if ladder[-1] < int(max_batch):
        # Non-power-of-two base: doublings alone stop short of the
        # requested width; cap the ladder with it so the bulk phase
        # dispatches as wide as configured.
        ladder.append(top)
    return tuple(ladder)


def pick_bucket(ladder: tuple, width: int) -> int:
    """Smallest ladder rung that covers ``width`` frontier rows (the
    widest rung when none does — the frontier then drains over several
    full-width waves)."""
    for b in ladder:
        if width <= b:
            return b
    return ladder[-1]


def succ_bucket_ladder(full: int, base: int = 256) -> tuple:
    """The successor-side output ladder: how many compacted novel rows a
    wave program emits. Rungs are ``base`` times powers of FOUR, capped
    by ``full`` (= the wave's B*F successor space, always the last rung
    so a worst-case wave fits). The x4 spacing bounds the extra compiles
    at O(log4 full) per batch bucket while still letting the common
    small-novel-set wave skip most of the full-width compaction gather
    and output traffic (GPUexplore's successor-collapse observation:
    most of a wave's candidate stream is duplicate or already visited).
    """
    full = max(1, int(full))
    if full <= base:
        return (full,)
    rungs = []
    k = base
    while k < full:
        rungs.append(k)
        k *= 4
    rungs.append(full)
    return tuple(rungs)


class TpuBfsChecker(Checker):
    """Runs BFS waves on the default JAX device (TPU when present)."""

    #: wave-event ``engine`` id (obs schema); one per engine class.
    _ENGINE_ID = "classic"

    #: whether this engine can bound its wave outputs with the successor
    #: ladder (per-wave engines: outputs cross to the host, so K-bounded
    #: gathers and transfers pay off; the fused engines append only the
    #: admitted rows on device, a chunk at a time — ``append_rows`` —
    #: and opt out).
    _SUCC_LADDER_CAPABLE = True

    #: whether jobs targeting this engine shape can be admitted into a
    #: shared multiplexed wave group (service/mux.py). Requires the
    #: per-wave host boundary: the mux splits every wave's outputs per
    #: tenant on the host before they reach counts/queues/discoveries.
    #: The fused engines keep frontiers and stats device-resident
    #: across multi-wave dispatches — there is no per-wave boundary to
    #: split at — and opt out (they still share compiled programs via
    #: the jit cache, just not dispatches).
    _MUX_CAPABLE = True

    #: whether the tiered store may evict visited partitions out of
    #: this engine's device table (stateright_tpu.store). Requires the
    #: per-wave host boundary — each wave's novel block is filtered
    #: against the spilled partitions BEFORE it reaches counts/queues.
    #: The fused engines dedup entirely on device across multi-wave
    #: dispatches (a host filter would come too late: re-admitted rows
    #: would already be re-expanded) and opt out; their device relief
    #: valve is the arena-span spill instead (see fused.py).
    _VISITED_SPILL_CAPABLE = True

    def __init__(self, builder, batch_size: int = 1024,
                 device_model: Optional[DeviceModel] = None,
                 table_capacity: int = 1 << 16,
                 checkpoint_path: Optional[str] = None,
                 checkpoint_every_waves: int = 64,
                 resume_from: Optional[str] = None,
                 pipeline: Optional[bool] = None,
                 max_batch_size: Optional[int] = None,
                 succ_ladder: Optional[bool] = None,
                 pack_arena: Optional[bool] = None,
                 tier_device_bytes: Optional[int] = None,
                 tier_host_bytes: Optional[int] = None,
                 tier_dir: Optional[str] = None,
                 tier_partitions: Optional[int] = None,
                 program_cache=None,
                 program_key: Optional[tuple] = None,
                 trace_path: Optional[str] = None,
                 async_io: Optional[bool] = None):
        # Before this process's first compile: JAX decides once whether
        # the persistent cache is in use (jit_cache.py).
        enable_persistent_jit_cache()
        model = builder._model
        # Cross-instance compiled-program sharing (jit_cache.
        # WaveProgramCache): armed only when BOTH a cache and a model
        # key are supplied — the key certifies that two engines' device
        # models are semantically identical (the job service derives it
        # from the corpus registry name + canonical params), which is
        # the safety condition for sharing a traced program. Ad-hoc
        # models never share.
        self._prog_cache = program_cache if program_key is not None \
            else None
        self._prog_key = tuple(program_key) if program_key is not None \
            else None
        self._prog_hits = 0
        self._prog_misses = 0
        # Per-run trace destination override: the job service gives
        # every job its own JSONL file (GET /jobs/<id>/trace streams
        # it); None follows the process-global STpu_TRACE env.
        self._trace_path = trace_path
        # Cooperative preemption (the job service's DELETE /jobs/<id>):
        # the wave loop checks the event at its dispatch boundary,
        # drains any in-flight wave, and stops — a safe point, so the
        # end-of-run checkpoint is a valid resume image.
        self._preempt_evt = threading.Event()
        self.preempted = False
        # Software-pipeline one wave deep on accelerators (hides the
        # host-side processing behind device compute); on the CPU backend
        # host and "device" share cores, so overlap only adds overhead.
        self._pipeline = (jax.default_backend() != "cpu"
                          if pipeline is None else bool(pipeline))
        if device_model is None:
            factory = getattr(model, "device_model", None)
            if factory is None:
                raise TypeError(
                    f"{type(model).__name__} does not define device_model(); "
                    "the TPU engine needs a DeviceModel (fixed-width state "
                    "encoding + jittable step). Use spawn_bfs()/spawn_dfs() "
                    "for host-only models.")
            device_model = factory()
        self._model = model
        self._dm = device_model
        self._properties = model.properties()
        self._use_symmetry = builder._symmetry is not None
        if self._use_symmetry:
            zero = jnp.zeros((device_model.state_width,), jnp.uint32)
            if device_model.representative(zero) is None:
                raise NotImplementedError(
                    "symmetry() on the TPU engine requires "
                    "DeviceModel.representative()")
        self._target_state_count = builder._target_state_count
        self._visitor = (as_visitor(builder._visitor)
                         if builder._visitor else None)
        self._B = batch_size
        self._buckets = batch_bucket_ladder(batch_size, max_batch_size)
        self._B_max = self._buckets[-1]
        self._F = device_model.max_fanout
        self._W = device_model.state_width
        # Packed storage row format (tpu/packing.py): states are
        # COMPUTED as uint32[W] registers but STORED (frontier blocks,
        # arena, shard exchange, checkpoints) as uint32[Wrow] packed
        # rows when the model declares narrow lanes. Like the pipeline
        # knob, the default is backend-aware: on accelerators the rows
        # live in HBM and the codec buys back 2-4x the bytes per state;
        # on the XLA:CPU fallback the working set is cache-resident and
        # the codec is pure compute overhead (measured ~15% on the
        # classic paxos headline — MEASUREMENTS round 9), so auto means
        # off there. pack_arena=True/False forces either way (a
        # performance schedule, never semantics: the wave unpacks to
        # the exact same registers either way).
        from .packing import compile_layout

        # getattr: bring-your-own device models duck-type the contract
        # and may predate the lane_bits hook — no declaration means the
        # conservative 32-bits-per-lane identity layout.
        lane_bits = getattr(device_model, "lane_bits", lambda: None)()
        self._layout = compile_layout(lane_bits, self._W)
        if pack_arena is None:
            pack_arena = jax.default_backend() != "cpu"
        self._pack_on = bool(pack_arena) and self._layout.packs
        self._Wrow = self._layout.packed_width if self._pack_on else self._W
        # Successor-side output ladder (classic per-wave engines only:
        # the fused engines append on device — see
        # _SUCC_LADDER_CAPABLE). Results are K-independent (overflowed
        # waves regather losslessly), so this is purely a performance
        # schedule, like the input bucket ladder.
        self._succ_ladder_on = (self._SUCC_LADDER_CAPABLE
                                and (True if succ_ladder is None
                                     else bool(succ_ladder)))
        #: recent (batch bucket, novel rows) pairs — the history the
        #: scheduler sizes the next wave's output rung from.
        self._succ_hist: deque = deque(maxlen=8)
        if len(self._properties) > 32:
            raise NotImplementedError("at most 32 properties on device")

        # Which properties evaluate on device vs. host-side fallback.
        device_props = device_model.device_properties()
        self._prop_fns = [device_props.get(p.name)
                          for p in self._properties]
        # Subclass support veto (e.g. the fused engine cannot host-eval)
        # runs BEFORE the warning and the heavy table/checkpoint work, so
        # an engine fallback neither warns twice nor initializes twice.
        self._check_support()
        for p, fn in zip(self._properties, self._prop_fns):
            if fn is None:
                warnings.warn(
                    f"property {p.name!r} has no device predicate; "
                    "falling back to host evaluation per wave (slow)",
                    stacklevel=2)

        self._ckpt_path = checkpoint_path
        self._ckpt_every = max(1, int(checkpoint_every_waves))
        self._discoveries: Dict[str, int] = {}
        self._ebits_all = 0
        self._eventually_idx: List[int] = []
        for i, p in enumerate(self._properties):
            if p.expectation is Expectation.EVENTUALLY:
                self._ebits_all |= 1 << i
                self._eventually_idx.append(i)
        self._pending: deque = deque()
        self._parents: Dict[int, Optional[int]] = {}
        self._parents_consumed = 0

        # Tiered state store (stateright_tpu.store): armed by explicit
        # kwargs or the STpu_TIER_* env knobs, the shared NULL_STORE
        # otherwise (one attribute check per wave — the tracer/faults
        # contract). Created BEFORE any checkpoint load: a v5 resume
        # re-attaches cold segments through it.
        self._store = store_from_config(
            device_bytes=tier_device_bytes, host_bytes=tier_host_bytes,
            segment_dir=tier_dir, n_partitions=tier_partitions,
            owner=self, meta={"model_name": type(model).__name__,
                              "state_width": self._W,
                              "use_symmetry": self._use_symmetry})

        # Asynchronous host I/O (round 17): ONE bounded background
        # writer per engine — checkpoint generations and the store's
        # cold-segment spills share it, so the safe-point join rule
        # (`_write_checkpoint` joins before capturing the next
        # snapshot) covers every off-thread write at once. Unset
        # follows the STpu_ASYNC_IO env knob; knob-off is the inline
        # SyncWriter and every path behaves exactly as before.
        from ..io.async_io import writer_from_config

        self._aio = writer_from_config(
            async_io, name=f"stpu-aio-{self._ENGINE_ID}")
        self._store.attach_async(self._aio)
        #: seconds the wave loop spent blocked on host I/O since the
        #: last wave event (joins + inline write time) — drained into
        #: the v10 ``io_stall_s`` wave gauge by ``_take_io_stall``.
        self._io_stall_s = 0.0
        self._ckpt_gen = 0

        if resume_from is not None:
            visited_fps = self._load_checkpoint(resume_from)
        else:
            # Seed from init states (bfs.rs:43-66).
            init_states = [s for s in model.init_states()
                           if model.within_boundary(s)]
            self._state_count = len(init_states)
            init_rep_fps = set()
            init_vecs: List[np.ndarray] = []
            init_fps: List[int] = []
            for s in init_states:
                vec = np.asarray(device_model.encode(s), np.uint32)
                fp = host_fp64(vec)
                if self._use_symmetry:
                    rep = np.asarray(
                        device_model.representative(jnp.asarray(vec)),
                        np.uint32)
                    rep_fp = host_fp64(rep)
                else:
                    rep_fp = fp
                if rep_fp in init_rep_fps:
                    continue
                init_rep_fps.add(rep_fp)
                init_vecs.append(vec)
                init_fps.append(fp)
            # Pending is a queue of BLOCKS (vecs, fps, ebits arrays); the
            # parent log mirrors it per wave and materializes into a dict
            # only when a path is reconstructed.
            fps_arr = np.array(init_fps, np.uint64)
            if init_vecs:
                seed = np.stack(init_vecs).astype(np.uint32)
                if self._pack_on:
                    # Cold-path contract check: a wrong lane_bits()
                    # declaration dies here, not as silent truncation.
                    self._layout.check_fits(seed)
                self._pending.append((
                    self._pack_np(seed), fps_arr,
                    np.full(len(init_fps), self._ebits_all, np.uint32)))
            self._unique_count = len(init_fps)
            self._parent_log: List = [(fps_arr, None)]
            visited_fps = np.fromiter(
                init_rep_fps, np.uint64, len(init_rep_fps))

        # Device-resident visited table: open-addressing uint64 hash
        # table, padded with SENTINEL. Capacity rounds UP so a caller
        # pre-sizing for a known run (bench.py) never recompiles mid-run.
        self._capacity = 1 << max(12, (int(table_capacity) - 1).bit_length())
        visited_fps = self._spill_seed(visited_fps)
        while self._capacity < (4 * len(visited_fps)
                                + 2 * self._B_max * self._F):
            self._capacity *= 2
        self._visited = self._new_table(visited_fps)
        self._wave_cache: dict = {}

        self._lock = threading.Lock()
        self._done = threading.Event()
        self._error: Optional[BaseException] = None
        #: (monotonic time, cumulative state_count) samples: one at run
        #: start, then one per wave. Waves after a table growth recompile,
        #: so steady-state throughput is best measured with a pre-sized
        #: table over entries [2:] (see bench.py).
        self.wave_log: list = []
        #: one dict per processed dispatch: ``{"t", "states", "bucket",
        #: "compiled", "waves", "inflight"}``. ``compiled`` marks an
        #: entry whose wall-clock interval contained a first-use XLA
        #: compile — under pipelined dispatch a new bucket's compile
        #: runs on the host BETWEEN stats reads, so the flag is
        #: interval-attributed (``_note_compile``/``_take_compile``),
        #: not launch-attributed; bench.py excludes flagged intervals
        #: from the steady rate. See ``scheduler_stats``.
        self.dispatch_log: list = []
        self._compile_dirty = False
        #: wall seconds spent in ahead-of-time XLA compiles (``_aot``) —
        #: the scheduler's bucket-ladder compile budget, reported by
        #: ``scheduler_stats`` so bench runs can attribute it.
        self.compile_sec = 0.0
        #: (end time, duration) per AOT compile; compiles run on the
        #: host thread between stats reads, so each lies inside exactly
        #: one dispatch_log interval — bench.py subtracts them from that
        #: interval's wall when computing the steady rate.
        self.compile_log: list = []
        #: run tracer (obs subsystem): a live JSONL writer when
        #: ``STpu_TRACE`` is set, the shared null tracer otherwise. Hot
        #: paths guard every emit with ``.enabled`` so the disabled
        #: subsystem costs one attribute check per dispatch.
        self._tracer = tracer_from_env(self._ENGINE_ID, path=self._trace_path, meta={
            "model": type(model).__name__,
            "batch_size": self._B,
            "bucket_ladder": list(self._buckets),
            "table_capacity": self._capacity,
            "max_fanout": self._F,
            "state_width": self._W})
        #: fault-injection plan (resilience subsystem): the live
        #: ``STpu_FAULTS`` plan, or the shared disarmed NULL_PLAN —
        #: every hook is guarded by ``.active``, so the unarmed
        #: subsystem costs one attribute check per dispatch (same
        #: contract as the tracer; MEASUREMENTS round-10).
        self._faults = fault_plan_from_env()
        #: always-on flight recorder (obs subsystem): the ring holds a
        #: reference to each dispatch_log entry — which this engine
        #: builds regardless of tracing — so recording is one guarded
        #: append, and a failed run dumps the last events to a
        #: postmortem file the Supervisor attaches to its retry/abort
        #: events. ``STpu_FLIGHT=0`` disarms it to the shared null.
        self._flight = recorder_from_env(
            f"{self._ENGINE_ID}-{os.getpid()}")
        #: the newest postmortem dump path (a failed run sets it).
        self.flight_dump: Optional[str] = None
        #: service observability facade (obs/hist.py): latency
        #: histograms + SLO burn windows + the slow-wave anomaly
        #: detector, fed with the dispatch_log entry the wave loop
        #: already builds. Disarmed (no ``STpu_HIST``/``STpu_SLO``/
        #: ``STpu_ANOMALY``) it is the shared NULL_OBS — one attribute
        #: check per dispatch, same contract as the tracer.
        self._wave_obs = wave_obs_from_env(self._ENGINE_ID)
        if self._wave_obs.enabled and self._flight.armed:
            # Postmortems carry the latency distribution at death.
            self._flight.set_hist_source(
                self._wave_obs.final_snapshot_event)
        #: continuous wave profiler (obs/prof.py): static XLA cost
        #: capture at compile + sampled roofline timing at dispatch.
        #: Disarmed (``STpu_PROF`` unset) it is the shared NULL_PROF —
        #: one attribute check per dispatch, same contract as the
        #: tracer.
        self._prof = prof_from_env(self._ENGINE_ID)
        self._pre_spawn_check()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    # -- Packed row helpers (tpu/packing.py) ------------------------------

    def _pack_np(self, rows: np.ndarray) -> np.ndarray:
        """Host-side pack to the storage row format (identity with
        packing off)."""
        return self._layout.pack_np(rows) if self._pack_on else rows

    def _unpack_np(self, rows: np.ndarray) -> np.ndarray:
        """Host-side unpack from the storage row format (identity with
        packing off)."""
        return self._layout.unpack_np(rows) if self._pack_on else rows

    def _wave_layout(self):
        """The layout the wave programs pack/unpack with (None = rows
        are stored unpacked and the programs skip the codec)."""
        return self._layout if self._pack_on else None

    def _check_support(self) -> None:
        """Subclass hook: veto unsupported configurations cheaply, before
        any heavy initialization (table build, checkpoint load)."""

    def _pre_spawn_check(self) -> None:
        """Subclass hook: validate configuration before the worker starts."""

    # -- Checkpoint / resume ----------------------------------------------
    #
    # The reference has no checkpointing (a killed run restarts from
    # scratch); here the (visited fingerprints, pending frontier blocks,
    # discoveries, parent map) tuple IS the whole checker state — states
    # are reconstructible by replay, so checkpoints are small and
    # engine-agnostic: a snapshot from the single-device engine can
    # resume onto the sharded engine and vice versa (each rebuilds its
    # own table layout and ownership split from the same data).

    def _pending_blocks(self) -> list:
        """The not-yet-expanded frontier as (vecs, fps, ebits) blocks
        (subclasses with their own queue layout override this).
        Paged-out blocks are materialized non-destructively — the
        snapshot needs the rows, the queue keeps the ref."""
        return [self._store.load_ref(b) if isinstance(b, FrontierRef)
                else b for b in self._pending]

    def _snapshot(self) -> dict:
        """Collects checkpoint arrays. Only call at a safe point: between
        waves inside the worker, or after the worker has stopped."""
        from ..checkpoint_format import make_header

        parents = self._parent_map()
        n = len(parents)
        child = np.fromiter(parents.keys(), np.uint64, n)
        parent = np.fromiter((0 if v is None else v
                              for v in parents.values()), np.uint64, n)
        rooted = np.fromiter((v is None for v in parents.values()), bool, n)
        blocks = self._pending_blocks()
        if blocks:
            vecs = np.concatenate([b[0] for b in blocks])
            fps = np.concatenate([b[1] for b in blocks])
            ebits = np.concatenate([b[2] for b in blocks])
        else:
            vecs = np.zeros((0, self._Wrow), np.uint32)
            fps = np.zeros(0, np.uint64)
            ebits = np.zeros(0, np.uint32)
        visited = np.asarray(self._visited).reshape(-1)
        visited = visited[visited != SENTINEL]
        # Tiered store (checkpoint format v5): the snapshot's visited
        # section carries hot + warm; COLD segments travel by content
        # hash — a checkpoint of a spilled run moves only hot+warm
        # bytes, the segments already on disk are not rewritten.
        store_refs = None
        if self._store.active:
            warm = self._store.warm_fps()
            if len(warm):
                visited = np.concatenate([visited, warm])
            store_refs = self._store.checkpoint_refs()
        # Canonical order (round 16): the table scan above reflects
        # probe-slot placement, which depends on capacity growth
        # history — sorting makes the section a pure function of the
        # visited SET. Resume reinserts via host_table_insert, so the
        # on-disk order was never semantic; canonicalizing it is what
        # lets a multiplexed tenant's checkpoint match its solo twin
        # byte for byte.
        visited = np.sort(visited)
        # Pending rows persist in the storage row format; the header
        # self-describes the layout so ANY engine (packed or not, device
        # or native) can unpack on resume (checkpoint_format v2).
        header = make_header(
            model_name=type(self._model).__name__, state_width=self._W,
            state_count=self._state_count,
            unique_count=self._unique_count,
            use_symmetry=self._use_symmetry,
            discoveries=self._discoveries,
            row_format="packed" if self._pack_on else "u32",
            lane_bits=self._layout.specs if self._pack_on else None,
            packed_width=self._Wrow if self._pack_on else None,
            store=store_refs)
        return dict(header=header,
                    visited=visited, pending_vecs=vecs, pending_fps=fps,
                    pending_ebits=ebits, parent_child=child,
                    parent_parent=parent, parent_rooted=rooted)

    def _write_checkpoint(self, path: str) -> None:
        """Writes one checkpoint generation at a safe point. Async
        (round 17): join any still-pending write FIRST — a failure
        injected on the writer thread (``torn_ckpt``, ``ckpt_crc``,
        ``disk_full``) re-raises here, on the wave-loop thread, where
        the Supervisor/flight machinery expects it — then capture the
        snapshot arrays synchronously (content stays bit-identical to
        a sync write) and hand only the CRC/compress/rotate/rename to
        the writer. One FIFO thread + join-before-next-submit keeps
        generation ordering and keep-last-2 rotation exactly as the
        sync path. Sync (knob off): ``submit`` runs inline and this is
        byte-for-byte the pre-round-17 write."""
        from ..checkpoint_format import write_atomic

        t0 = time.monotonic()
        self._aio.join()
        payload = self._snapshot()
        self._ckpt_gen += 1
        gen = self._ckpt_gen
        tracer = self._tracer
        if tracer.enabled:
            tracer.event("ckpt_begin", gen=gen, path=path,
                         **{"async": bool(self._aio.enabled)})

        def _land() -> None:
            w0 = time.monotonic()
            write_atomic(path, payload)
            if tracer.enabled:
                tracer.event("ckpt_done", gen=gen, path=path,
                             write_s=round(time.monotonic() - w0, 6))

        self._aio.submit(_land, kind="checkpoint")
        self._io_stall_s += time.monotonic() - t0

    def _take_io_stall(self):
        """Drains the accumulated wave-loop I/O stall into one wave
        event (v10 ``io_stall_s``)."""
        s, self._io_stall_s = self._io_stall_s, 0.0
        return round(s, 6)

    def checkpoint(self, path: str) -> None:
        """Writes a resumable snapshot. Valid once the run has stopped
        (done, all-discovered, or target_state_count reached); while
        running, use the ``checkpoint_path`` knob for periodic safe-point
        snapshots instead."""
        if not self._done.is_set():
            raise RuntimeError(
                "checkpoint() while the checker is running would race the "
                "wave loop; pass checkpoint_path=... to spawn_tpu_bfs for "
                "periodic snapshots, or join() first")
        if self._error is not None:
            # A wave died after taking a batch but before streaming its
            # successors back: those states are in the visited table but
            # not in pending, so a snapshot now would permanently lose
            # their subtrees on resume. restart_from() clears this flag
            # on a successful in-place resume.
            raise RuntimeError(
                "checkpoint() after a failed run would snapshot a torn "
                "frontier; resume from the last periodic checkpoint "
                "(restart_from) instead") from self._error
        self._write_checkpoint(path)
        # Durability contract: the file exists (or the failure raised
        # here) when this returns, knob on or off.
        self._aio.join()

    def restart_from(self, path: str) -> "TpuBfsChecker":
        """In-place crash recovery: discards the failed run's (torn)
        in-memory state, reloads the snapshot at ``path``, CLEARS the
        failed-run flag, and restarts the worker — on this same
        instance, so the compiled wave-program cache survives and a
        recovery costs zero recompiles. This is the supervisor's
        preferred retry path (``resilience.supervisor``). Only valid
        once the worker has stopped; a successful restarted run makes
        ``checkpoint()`` usable again."""
        if not self._done.is_set():
            raise RuntimeError(
                "restart_from() while the checker is running; join() "
                "(or wait for the failure) first")
        self._thread.join()
        # The failed-run flag: cleared here, re-set only if the
        # restarted run fails again. The background writer drains and
        # drops any still-captured failure the same way — the resume
        # supersedes whatever generation died mid-flight.
        self._aio.reset()
        self._io_stall_s = 0.0
        self._error = None
        self._discoveries = {}
        self._pending = deque()
        self._parents = {}
        self._parent_log = []
        self._parents_consumed = 0
        self._succ_hist.clear()
        self.wave_log = []
        self.dispatch_log = []
        self._compile_dirty = False
        self._reset_engine_state()
        if self._store.active:
            # Warm/cold tiers rebuild from the checkpoint's v5 refs
            # (attached by _load_checkpoint), not the failed run's.
            self._store.reset()
        visited_fps = self._load_checkpoint(path)
        visited_fps = self._spill_seed(visited_fps)
        while self._capacity < (4 * len(visited_fps)
                                + 2 * self._B_max * self._F):
            self._capacity *= 2
        self._visited = self._new_table(visited_fps)
        self._tracer = tracer_from_env(
            self._ENGINE_ID, path=self._trace_path, meta={
                "model": type(self._model).__name__,
                "restarted_from": path})
        # The preempt EVENT survives a restart on purpose: a preempt
        # that raced a crash (requested while the failed run was down)
        # still targets the JOB, so the recovered run must honor it at
        # its first wave boundary — drain, checkpoint, stop — instead
        # of silently running to completion. Only the outcome flag
        # resets.
        self.preempted = False
        self._done = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()
        return self

    def _reset_engine_state(self) -> None:
        """Subclass hook: drop engine-specific run state (device
        arenas, per-shard queues) before a restart_from reload."""

    def _load_checkpoint(self, path: str) -> np.ndarray:
        """Restores pending/counts/discoveries/parents; returns the
        visited fingerprints for table seeding."""
        from ..checkpoint_format import (load_checkpoint, pending_rows,
                                         validate_header)

        with load_checkpoint(path) as data:
            header = validate_header(
                data, model_name=type(self._model).__name__,
                state_width=self._W, use_symmetry=self._use_symmetry)
            self._state_count = int(header["state_count"])
            self._unique_count = int(header["unique_count"])
            self._discoveries = {k: int(v) for k, v
                                 in header["discoveries"].items()}
            # pending_rows unpacks whatever row format the WRITER used
            # (self-described in the header); re-pack to THIS engine's
            # storage format — cross-format resume is how v1 unpacked
            # snapshots land on packed engines and vice versa. The
            # cold-path contract check runs first: a snapshot from an
            # engine without this model's lane_bits() bounds must fail
            # loudly here, not resume from silently truncated rows.
            vecs = pending_rows(data, header, self._W)
            if self._pack_on:
                self._layout.check_fits(vecs)
            vecs = self._pack_np(vecs)
            fps = data["pending_fps"]
            ebits = data["pending_ebits"]
            if len(fps):
                self._pending.append((vecs, fps, ebits))
            child = data["parent_child"]
            parent = data["parent_parent"]
            rooted = data["parent_rooted"]
            self._parents = {
                int(c): (None if r else int(p))
                for c, p, r in zip(child.tolist(), parent.tolist(),
                                   rooted.tolist())}
            self._parent_log = []
            visited = data["visited"]
            refs = header.get("store")
            if refs:
                if self._store.active and self._VISITED_SPILL_CAPABLE:
                    # v5 resume: re-attach the referenced cold segments
                    # (CRC + content-hash verified, with the rotation-
                    # predecessor fallback) — only hot+warm rows enter
                    # the device table.
                    self._store.attach_refs(
                        refs, base_dir=os.path.dirname(
                            os.path.abspath(path)))
                else:
                    # No store on this side (or an engine that cannot
                    # host-filter): materialize the cold rows into the
                    # device tier — slower, never wrong.
                    from ..store.tiered import load_cold_refs

                    cold = load_cold_refs(refs, base_dir=os.path.dirname(
                        os.path.abspath(path)))
                    if len(cold):
                        visited = np.concatenate(
                            [np.asarray(visited, np.uint64), cold])
            return visited

    # -- Device wave program ---------------------------------------------

    def _new_table(self, fps) -> jax.Array:
        table = np.full(self._capacity, SENTINEL, np.uint64)
        host_table_insert(table, np.fromiter(
            (int(f) for f in fps), np.uint64, len(fps)))
        # Device-tier occupancy (== unique_count unless the tiered
        # store has evicted partitions): what growth/load-factor gate
        # on.
        self._resident = len(fps)
        return jax.device_put(jnp.asarray(table))

    def _cached_program(self, key: tuple, build):
        """Two-level compiled-program lookup: the per-instance
        ``_wave_cache`` first, then — when the engine carries a
        registry-certified ``program_key`` — the process-wide shared
        cache (``jit_cache.WaveProgramCache``), so the Nth same-model
        job reuses the first job's executables instead of recompiling.
        ``build()`` must return a ready (AOT-compiled where supported)
        callable; the shared cache serializes concurrent builders per
        key. Hits cost no compile, so neither ``compile_sec`` nor the
        dispatch-interval ``compiled`` flags move — the cold/warm
        difference is exactly what job latency A/Bs measure."""
        cached = self._wave_cache.get(key)
        if cached is not None:
            return cached
        if self._prog_cache is not None:
            shared_key = (self._prog_key, self._ENGINE_ID,
                          self._pack_on, self._use_symmetry) + key
            prog, hit = self._prog_cache.get_or_build(shared_key, build)
            if hit:
                self._prog_hits += 1
            else:
                self._prog_misses += 1
        else:
            prog = build()
        if self._prof.enabled:
            # Static cost capture (obs/prof.py): reads the compiled
            # executable's cost/memory analysis at most once per
            # program per process — a shared-cache hit finds the first
            # builder's record through the same key, so hits pay a
            # dict lookup, never a re-lower.
            self._prof.capture(self._prof_key(key), prog)
        self._wave_cache[key] = prog
        return prog

    def _prof_key(self, key: tuple) -> str:
        """The profiler's canonical program identity (obs/prof.py):
        engine id + a short digest of the shared-cache prefix (the
        model's program key and the executable-determining knobs) +
        the instance key. Process-stable, so every engine instance of
        one model/config derives the same string and shared-cache hits
        find the first builder's cost record."""
        prefix = (self._prog_key, self._pack_on, self._use_symmetry)
        digest = hashlib.blake2s(repr(prefix).encode(),
                                 digest_size=4).hexdigest()
        return f"{self._ENGINE_ID}|{digest}|{key!r}"

    def _wave_fn(self, capacity: int, batch: Optional[int] = None,
                 out_rows: Optional[int] = None):
        """Builds (and caches) the jitted wave program for a (batch,
        table size, output rung) bucket."""
        B = self._B if batch is None else batch
        K = B * self._F if out_rows is None else out_rows

        def build():
            jitted = build_wave(self._dm, B, capacity, self._prop_fns,
                                self._use_symmetry, out_rows=K,
                                layout=self._wave_layout())
            sds = jax.ShapeDtypeStruct
            return self._aot(jitted, (
                sds((B, self._Wrow), jnp.uint32), sds((B,), jnp.bool_),
                sds((capacity,), jnp.uint64)))

        return self._cached_program((B, capacity, K), build)

    def _succ_full_rows(self, B: int) -> int:
        """The wave's full successor space — the output ladder's top
        rung (per shard on the sharded engine, which overrides this)."""
        return B * self._F

    def kernel_path(self) -> str:
        """The successor path the wave programs run: the XLA op
        ladder, ``"xla"`` (the wave events' ``kernel_path``)."""
        return "xla"

    def _pick_out_rows(self, B: int) -> int:
        """Picks the output rung for the next wave at batch bucket
        ``B`` from the novel-count history: twice the worst recent
        novel set (scaled when the history was measured at a narrower
        batch), rounded up the ladder. Until the history WINDOW fills —
        or with the ladder disabled — the full width is used: a sub-full
        rung costs one XLA compile per (B, K), which only a run long
        enough to have filled the window will amortize. Correctness
        never depends on the guess (an overflowed wave regathers
        losslessly); this only sets how often the regather path is
        paid."""
        full = self._succ_full_rows(B)
        if (not self._succ_ladder_on
                or len(self._succ_hist) < self._succ_hist.maxlen):
            return full
        ladder = succ_bucket_ladder(full)
        if len(ladder) == 1:
            return full
        want = 0
        for b, novel in self._succ_hist:
            want = max(want, novel * -(-B // b))
        return pick_bucket(ladder, 2 * want + 16)

    def _regather_fn(self, batch: int, out_rows: int):
        """The overflow-recovery program for a (batch, rung) pair: a
        pure re-expansion + mask-driven compaction at a rung that fits
        (no table access — the wave already inserted every novel
        candidate; only the truncated outputs are recomputed)."""
        def build():
            jitted = build_regather(self._dm, batch, out_rows,
                                    self._use_symmetry,
                                    layout=self._wave_layout())
            sds = jax.ShapeDtypeStruct
            return self._aot(jitted, (
                sds((batch, self._Wrow), jnp.uint32),
                sds((batch,), jnp.bool_),
                sds((batch * self._F,), jnp.bool_)))

        return self._cached_program(("regather", batch, out_rows), build)

    def _note_compile(self, compiled: bool) -> None:
        """Marks the current processing interval compile-contaminated."""
        if compiled:
            self._compile_dirty = True

    def _take_compile(self) -> bool:
        dirty = self._compile_dirty
        self._compile_dirty = False
        return dirty

    def _aot(self, jitted, arg_specs):
        """Ahead-of-time compiles a jitted program from
        ``ShapeDtypeStruct`` specs, so LAUNCHES never carry an XLA
        compile: under pipelined dispatch a lazy first call would embed
        the compile in whatever processing interval happens to be open,
        corrupting the steady-rate attribution. The compile cost is
        accounted in ``compile_sec`` instead. Falls back to the lazy
        jitted callable (interval-flagged via ``_note_compile``) where
        lowering is unsupported."""
        t0 = time.monotonic()
        try:
            with warnings.catch_warnings():
                warnings.filterwarnings(
                    "ignore",
                    message="Some donated buffers were not usable")
                compiled = jitted.lower(*arg_specs).compile()
        except Exception:  # noqa: BLE001 — lazy path stays correct
            self._note_compile(True)
            return jitted
        now = time.monotonic()
        self.compile_sec += now - t0
        self.compile_log.append((now, now - t0))
        return compiled

    def scheduler_stats(self) -> dict:
        """The adaptive wave scheduler's run telemetry: the configured
        bucket ladder, how many dispatches each bucket served, how many
        paid a first-use compile, and the deepest dispatch pipelining
        achieved (0 = fully synchronous).

        Every figure is a VIEW over the wave-event stream
        (``dispatch_log`` — the same unified per-dispatch records the
        obs tracer serializes under ``STpu_TRACE``); there is no
        parallel bookkeeping to drift out of sync."""
        with self._lock:
            log = list(self.dispatch_log)
        succ_total = sum(e["successors"] for e in log)
        cand_total = sum(e["candidates"] for e in log)
        overflows = sum(1 for e in log if e["overflow"])
        # Kernel occupancy: frontier rows actually processed vs the
        # padded rows the wave programs dispatched (bucket width x BFS
        # levels) — the figure the ladder's K choice is judged against
        # (a half-empty wave pays full kernel time either way). A
        # zero-wave entry (a pipelined fused dispatch that no-opped at
        # a rest point) contributes nothing to either side — it ran no
        # kernel.
        rows_total = sum(e.get("rows") or 0 for e in log)
        # bucket is PER SHARD on the sharded engines while rows counts
        # every shard's valid slots, so the padded denominator scales
        # by the mesh (slots = 1 on the single-device engines).
        slots = int(getattr(self, "_n_shards", getattr(self, "_n", 1)))
        padded_total = sum(e["bucket"] * e["waves"] * slots
                           for e in log)
        buckets: Dict[str, int] = {}
        out_rows: Dict[str, int] = {}
        for e in log:
            k = str(e["bucket"])
            buckets[k] = buckets.get(k, 0) + 1
            if e.get("out_rows") is not None:
                r = str(e["out_rows"])
                out_rows[r] = out_rows.get(r, 0) + 1
        return {
            "bucket_ladder": list(self._buckets),
            "bucket_dispatches": buckets,
            "dispatches": len(log),
            "bucket_compiles": sum(1 for e in log if e["compiled"]),
            "compile_sec": round(self.compile_sec, 3),
            "max_inflight": max((e["inflight"] for e in log), default=0),
            # Successor-path telemetry (ISSUE 2): which output rungs the
            # ladder dispatched, how often a wave's novel set overflowed
            # its rung (and paid the logged regather), and how much of
            # the candidate stream the intra-wave local dedup collapsed
            # before the global table probe.
            "succ_ladder": {
                "enabled": self._succ_ladder_on,
                "out_rows_dispatches": out_rows,
                "overflow_redispatches": overflows,
                "occupancy": (round(rows_total / padded_total, 4)
                              if padded_total else 0.0),
            },
            "local_dedup": {
                "successors": succ_total,
                "distinct_candidates": cand_total,
                "collapse_ratio": (round(1.0 - cand_total
                                         / max(succ_total, 1), 4)
                                   if succ_total else 0.0),
            },
            # Packed-arena telemetry (ISSUE 4): the storage row format
            # and the byte high-water marks, read off the same wave
            # event stream as everything else.
            "packing": {
                "enabled": self._pack_on,
                "state_width": self._W,
                # What the layout CAN pack to (reported even when the
                # knob resolved off, so a CPU bench still records the
                # achievable cut) vs what this run actually stored.
                "packed_width": self._layout.packed_width,
                "row_width": self._Wrow,
                "bytes_per_state": 4 * self._Wrow,
                "bytes_per_state_packed": 4 * self._layout.packed_width,
                "bytes_per_state_unpacked": 4 * self._W,
                "ratio": round(self._W / self._Wrow, 3),
                "packable_ratio": round(
                    self._W / self._layout.packed_width, 3),
                "arena_bytes_high_water": max(
                    (e.get("arena_bytes") or 0 for e in log),
                    default=0) or None,
                "table_bytes_high_water": max(
                    (e.get("table_bytes") or 0 for e in log),
                    default=0) or None,
            },
            # Tiered-store telemetry (ISSUE 8): per-tier occupancy,
            # spill/page-in counters, and the resident ratio — the
            # graceful-degradation record.
            "store": self.store_stats(),
            # Cross-job compiled-program sharing (ISSUE 9): how many of
            # this run's program lookups the process-wide cache served
            # vs built. A warm-cache job shows hits > 0 and
            # bucket_compiles == 0 — the service's amortization story.
            "program_cache": {
                "shared": self._prog_cache is not None,
                "hits": self._prog_hits,
                "misses": self._prog_misses,
            },
            # Asynchronous host I/O (ISSUE 13): the background writer's
            # ledger — pending writes, safe-point joins and their wait,
            # and the overlap seconds the knob bought (writer busy time
            # the wave loop did not wait for).
            "async_io": self._aio.stats(),
            # Service-level observability (ISSUE 14): rolling SLO
            # burn-window status (None when ``STpu_SLO`` is unset) and
            # the recent slow-wave anomaly verdicts (empty when
            # ``STpu_ANOMALY`` is unset).
            "slo": self._wave_obs.slo_status(),
            "anomalies": self._wave_obs.anomalies(),
            # Continuous wave profiler (ISSUE 18): sampled roofline
            # snapshots per compiled program (None when ``STpu_PROF``
            # is unset).
            "prof": (self._prof.stats() if self._prof.enabled
                     else None),
        }



    # -- Host orchestration loop -----------------------------------------

    def _run(self) -> None:
        try:
            self._run_waves()
            if self._ckpt_path is not None:
                self._write_checkpoint(self._ckpt_path)
            # Final safe point: the last generation (and any spill
            # still in flight) lands — or surfaces its writer-thread
            # failure as an ordinary engine error — before done.
            self._aio.join()
        except BaseException as e:  # surfaced at join()
            self._error = e
            if self._flight.armed:
                # The always-on postmortem: the ring's last waves,
                # dumped where a dark (untraced) run would otherwise
                # die without a trail. The Supervisor attaches this
                # path to its retry/abort events.
                self.flight_dump = self._flight.dump(
                    f"{type(e).__name__}: {e}")
        finally:
            if self._wave_obs.enabled:
                # A short run may never cross the snapshot cadence:
                # land the final histogram snapshot before run_end.
                self._wave_obs.close(self._tracer)
            self._tracer.close()
            self._done.set()

    def _take_batch(self, pending: deque, rows: int):
        """Assembles up to ``rows`` frontier rows from the block queue.

        The pending queue holds whole *blocks* (vecs, fps, ebits arrays) —
        one per producing wave — rather than per-state tuples, so batch
        assembly and new-state streaming are pure array ops with no
        per-state Python in the hot loop.
        """
        parts = []
        taken = 0
        while pending and taken < rows:
            if isinstance(pending[0], FrontierRef):
                # Page the block back in before it can dispatch; the
                # NEXT paged-out blocks (scanning a few entries deep)
                # go to the background reader so their disk reads
                # overlap this dispatch. With async_io on the window
                # widens from one-block-ahead to several (round 17:
                # the store-level prefetcher dedups by path, so the
                # same ref surfacing twice costs nothing).
                width = 4 if self._aio.enabled else 1
                depth = 32 if self._aio.enabled else 8
                ahead = []
                for i in range(1, min(len(pending), depth)):
                    if isinstance(pending[i], FrontierRef):
                        ahead.append(pending[i])
                        if len(ahead) >= width:
                            break
                pending[0] = self._store.fetch_frontier(
                    pending[0], prefetch=ahead or None)
            vecs, fps, ebits = pending[0]
            k = len(fps)
            take = min(k, rows - taken)
            if take == k:
                pending.popleft()
                parts.append((vecs, fps, ebits))
            else:
                parts.append((vecs[:take], fps[:take], ebits[:take]))
                pending[0] = (vecs[take:], fps[take:], ebits[take:])
            taken += take
        return parts, taken

    def _eval_host_conds(self, conds_out, batch_vecs, rows):
        """Reattaches device-evaluated conditions to property slots and
        fills host-fallback slots by decoding the batch rows in ``rows``.

        Decoding a row into a Python state object is the expensive part
        of the host fallback, so it happens lazily — only when at least
        one fallback slot exists — and at most ONCE per wave, with the
        decoded list shared across every fallback property (three
        host-only properties cost one decode pass, not three)."""
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
                # The batch rides in the storage row format; decode
                # needs real lanes — one unpack pass, shared across
                # every fallback property (like the decode itself).
                unpacked = self._unpack_np(batch_vecs)
                decoded = [(r, decode(unpacked[r])) for r in rows]
            cond = np.zeros(len(batch_vecs), bool)
            prop_cond = self._properties[i].condition
            for r, state in decoded:
                cond[r] = bool(prop_cond(model, state))
            conds.append(cond)
        return conds

    def _run_waves(self) -> None:
        """The host orchestration loop, software-pipelined one wave deep:
        while the device computes wave k, the host finishes processing
        wave k-1's outputs. Dispatch-ahead only happens when a FULL batch
        is already queued, so wave composition — and therefore BFS visit
        order, counts, and discovery identities — is bit-identical to a
        sequential loop (children always land at the queue tail; a
        partial batch means the loop drains first, exactly like the
        unpipelined schedule). Growth and checkpoints force a drain:
        both need the frontier + table at rest.

        Batch width is adaptive: each dispatch picks the smallest bucket
        of the power-of-two ladder that covers the queued frontier rows
        (``batch_bucket_ladder``), so a 40-row tail stops paying a
        full-width padded expand. Results are bucket-independent (the
        cross-B parity suite pins this)."""
        F = self._F
        properties = self._properties
        pending = self._pending
        self.wave_log.append((time.monotonic(), self._state_count))
        wave_index = 0
        last_ckpt = 0
        inflight = None

        while pending or inflight is not None:
            if self._preempt_evt.is_set():
                # Preemption (job service): drain the in-flight wave —
                # its table insertions are real, dropping its outputs
                # would tear the frontier — then stop at this safe
                # point; _run writes the resumable checkpoint.
                if inflight is not None:
                    self._process_wave(inflight)
                self.preempted = True
                return
            with self._lock:
                done = (len(self._discoveries) == len(properties)
                        # all properties discovered (bfs.rs:117)
                        or (self._target_state_count is not None
                            and self._state_count
                            >= self._target_state_count))
            if done:
                if inflight is not None:
                    # Drain: the dispatched wave's insertions are already
                    # in the visited table; dropping its outputs would
                    # tear the frontier (states visited but their
                    # subtrees never queued — fatal for checkpoints).
                    self._process_wave(inflight)
                return
            ckpt_due = (self._ckpt_path is not None
                        and wave_index - last_ckpt >= self._ckpt_every)
            # Two waves of headroom — see _needs_growth.
            growth_due = self._needs_growth()
            if inflight is None:
                if ckpt_due:
                    self._write_checkpoint(self._ckpt_path)  # safe point
                    last_ckpt = wave_index
                    ckpt_due = False
                if growth_due:
                    # Grow the table before it can overflow mid-wave.
                    self._grow_table()
                    growth_due = False

            # Count queued rows only until the dispatch threshold: O(1)
            # amortized instead of walking every pending block per wave.
            queued = 0
            for b in pending:
                queued += b.rows if isinstance(b, FrontierRef) \
                    else len(b[1])
                if queued >= self._B_max:
                    break
            next_wave = None
            # Dispatch-ahead only with a full widest-bucket batch queued
            # (wave composition then matches the sequential schedule).
            may_dispatch = (inflight is None
                            or (self._pipeline and queued >= self._B_max))
            if queued and may_dispatch and not growth_due and not ckpt_due:
                wave_index += 1
                next_wave = self._dispatch_wave(
                    pick_bucket(self._buckets, queued),
                    inflight=0 if inflight is None else 1)
            if inflight is not None:
                self._process_wave(inflight)
            inflight = next_wave

    def _dispatch_wave(self, batch: Optional[int] = None,
                       inflight: int = 0) -> tuple:
        """Assembles a batch and launches the wave program; returns the
        dispatch context with the (still device-resident, possibly
        unmaterialized) outputs."""
        B, W = (self._B if batch is None else batch), self._Wrow
        parts, n = self._take_batch(self._pending, B)
        batch_vecs = np.zeros((B, W), np.uint32)
        batch_fps = np.zeros(B, np.uint64)
        batch_ebits = np.zeros(B, np.uint32)
        row = 0
        for vecs, fps, ebits in parts:
            k = len(fps)
            batch_vecs[row:row + k] = vecs
            batch_fps[row:row + k] = fps
            batch_ebits[row:row + k] = ebits
            row += k
        valid = np.arange(B) < n

        K = self._pick_out_rows(B)
        prog = self._wave_fn(self._capacity, B, K)
        pkey = prof_s = t0 = None
        if self._prof.enabled:
            pkey = self._prof_key((B, self._capacity, K))
            if self._prof.should_sample(pkey):
                t0 = time.monotonic()
        outs = prog(
            jnp.asarray(batch_vecs), jnp.asarray(valid), self._visited)
        if t0 is not None:
            # Rest-point timing (obs/prof.py): forcing materialization
            # serializes this one dispatch against the pipeline — the
            # sampled 1/N price of a real device-time measurement.
            jax.block_until_ready(outs)
            prof_s = time.monotonic() - t0
        (conds_out, succ_count, cand_count, terminal, new_count,
         new_vecs, new_fps, new_parent, new_mask, overflow,
         self._visited) = outs
        meta = {"bucket": B, "inflight": inflight, "out_rows": K,
                "rows": n,
                "kernel_path": "xla",
                "expand_impl": "step"}
        if pkey is not None:
            # Internal riders for _process_wave — popped there before
            # the entry reaches the schema'd streams.
            meta["_prof_key"] = pkey
            if prof_s is not None:
                meta["_prof_s"] = prof_s
        return (conds_out, succ_count, cand_count, terminal, new_count,
                new_vecs, new_fps, new_parent, new_mask, overflow,
                batch_vecs, batch_fps, batch_ebits, valid, n, meta)

    def _process_wave(self, wave: tuple) -> None:
        """Materializes a dispatched wave's outputs and applies them to
        counts, discoveries, the parent log, and the frontier queue."""
        model = self._model
        properties = self._properties
        eventually_idx = self._eventually_idx
        (conds_out, succ_count, cand_count, terminal, new_count,
         new_vecs, new_fps, new_parent, new_mask, overflow, batch_vecs,
         batch_fps, batch_ebits, valid, n, meta) = wave
        if self._faults.active:
            # Before any count/queue mutation: a crash here models the
            # worst case (the dispatched wave's table insertions are
            # real, its outputs are lost — a torn frontier only a
            # checkpoint resume can repair).
            self._faults.crash("wave_crash", self._tracer,
                               wave=len(self.dispatch_log))

        conds = self._eval_host_conds(conds_out, batch_vecs, range(n))

        if self._visitor is not None:
            for r in range(n):
                self._visitor.visit(
                    model, self._reconstruct_path(int(batch_fps[r])))

        terminal = np.asarray(terminal)
        k = int(new_count)
        if bool(overflow):
            # The wave's novel set outgrew its output rung: the table
            # insertions are complete and the full novelty mask is an
            # output, so recover the truncated rows with a pure
            # regather at a rung that fits (logged — the scheduler's
            # history sizing is judged by how rarely this path runs).
            B = meta["bucket"]
            k2 = pick_bucket(succ_bucket_ladder(self._succ_full_rows(B)),
                             k)
            (new_vecs, new_fps, new_parent) = self._regather_fn(B, k2)(
                jnp.asarray(batch_vecs), jnp.asarray(valid), new_mask)
            meta = dict(meta, out_rows=k2, overflowed=True)
            if self._tracer.enabled:
                self._tracer.event("overflow_redispatch", bucket=B,
                                   out_rows=k2, novel=k)
        # Power-of-two slice lengths bound the number of
        # shape-specialized dispatch cache entries at O(log S).
        kb = min(max(1, 1 << (k - 1).bit_length()) if k else 0,
                 int(new_fps.shape[0]))
        new_vecs = np.asarray(new_vecs[:kb])[:k]
        new_fps = np.asarray(new_fps[:kb])[:k]
        parent_rows = np.asarray(new_parent[:kb])[:k]
        self._check_error_lane(new_vecs)

        # Tiered store: the device table only knows its RESIDENT rows,
        # so a spilled state that got re-generated looks novel on
        # device (and was re-admitted to the table). The batched probe
        # against the warm/cold partitions filters it here, BEFORE it
        # can touch counts, the parent log, or the queue — that filter
        # is what keeps a spilled run bit-identical to an all-in-device
        # run.
        k_dev = k
        if self._store.active and k and self._store.spilled_rows:
            present = self._store.probe(
                self._store_probe_fps(new_vecs, new_fps))
            if present.any():
                keep = ~present
                new_vecs = new_vecs[keep]
                new_fps = new_fps[keep]
                parent_rows = parent_rows[keep]
                k = len(new_fps)

        with self._lock:
            self._state_count += int(succ_count)
            self._resident += k_dev
            self._succ_hist.append((meta["bucket"], k_dev))
            now = time.monotonic()
            self.wave_log.append((now, self._state_count))
            # One unified wave event per dispatch (obs schema): the
            # in-memory dispatch_log entry IS the record the tracer
            # serializes, so scheduler_stats/bench read the same stream
            # a trace consumer does.
            entry = dict(
                meta, t=now, states=self._state_count,
                unique=self._unique_count + k, waves=1,
                compiled=self._take_compile(),
                successors=int(succ_count), candidates=int(cand_count),
                novel=k, capacity=self._capacity,
                # Occupancy is the DEVICE-resident count: with the
                # tiered store armed it can lag unique_count (spilled
                # partitions live warm/cold); without it they are
                # equal.
                load_factor=round(self._resident / self._capacity, 4),
                overflow=bool(meta.get("overflowed", False)),
                # Bandwidth gauges (obs schema v2): state-row bytes as
                # stored, plus the table footprint; the classic engine
                # keeps its frontier host-side, so arena_bytes is null.
                bytes_per_state=4 * self._Wrow, arena_bytes=None,
                table_bytes=self._capacity * 8,
                # v10: wave-loop host-I/O stall since the last wave
                # event (safe-point joins + inline write time).
                io_stall_s=self._take_io_stall())
            if self._store.active:
                # Tier occupancy gauges (obs schema v6).
                entry.update(self._store.gauges(),
                             tier_device_rows=self._resident,
                             tier_device_bytes=self._table_bytes(
                                 self._capacity))
            entry.pop("overflowed", None)
            if self._prof.enabled:
                # v13 cost stamping + (on sampled dispatches) the
                # profile_snapshot roofline event. The internal riders
                # never reach the dispatch log or the trace.
                self._prof.wave(entry, entry.pop("_prof_key", None),
                                entry.pop("_prof_s", None),
                                self._tracer, self._flight)
            self.dispatch_log.append(entry)
            if self._flight.armed:
                self._flight.record(entry)
            # Always/Sometimes discoveries: first failing/matching state
            # in queue order (bfs.rs:196-211).
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
                    self._discoveries[prop.name] = int(
                        batch_fps[rows[0]])
            # Eventually bits: clear satisfied, then flag terminal
            # states with remaining bits (bfs.rs:212-226, 265-272).
            ebits_after = batch_ebits.copy()
            for i in eventually_idx:
                ebits_after &= ~np.where(
                    conds[i], np.uint32(1 << i), np.uint32(0))
            for r in np.flatnonzero(terminal[:n] & (ebits_after[:n] != 0)):
                for i in eventually_idx:
                    prop = properties[i]
                    if (ebits_after[r] >> i) & 1 \
                            and prop.name not in self._discoveries:
                        self._discoveries[prop.name] = int(batch_fps[r])
            # Stream the new block into the queue + parent log — all
            # array ops, no per-state Python (bfs.rs:262 enqueue).
            if k:
                self._parent_log.append((new_fps, batch_fps[parent_rows]))
                self._unique_count += k
                self._pending.append(
                    (new_vecs, new_fps, ebits_after[parent_rows]))
        if self._store.active and k:
            # Host-tier frontier budget: page tail blocks out to disk
            # (they dispatch last; they page back in with prefetch).
            self._store.balance_frontier((self._pending,))
        if self._tracer.enabled:
            self._tracer.wave(entry)
        if self._wave_obs.enabled:
            self._wave_obs.wave(entry, self._tracer, self._flight)

    def _check_error_lane(self, new_vecs: np.ndarray) -> None:
        """Raises if any generated state tripped the model's error lane
        (e.g. a bounded-network overflow in an actor encoding)."""
        lane = self._dm.error_lane
        if lane is None or not new_vecs.size:
            return
        col = (self._layout.lane_np(new_vecs, lane) if self._pack_on
               else new_vecs[:, lane])
        if col.any():
            raise RuntimeError(
                f"device model error lane {lane} is set in a generated "
                "state: an encoding capacity was exceeded (for actor "
                "models: raise net_slots)")

    def _needs_growth(self) -> bool:
        """Whether the visited table needs to grow before the next
        dispatch: two waves of headroom against the load-factor-1/2
        bound (with one wave in flight, the resident count lags its
        unprocessed insertions by up to ``B_max*F``, and the next
        dispatch adds up to ``B_max*F`` more). ``_resident`` is the
        DEVICE-tier occupancy — equal to ``_unique_count`` until the
        tiered store evicts partitions."""
        return self._needs_growth_at(self._capacity)

    def _needs_growth_at(self, capacity: int) -> bool:
        """The growth predicate at a hypothetical capacity (shared by
        the real check, the grow-target simulation, and the tiered
        store's spill-vs-grow decision)."""
        return (self._resident + 2 * self._B_max * self._F
                > capacity // 2)

    def _simulate_grow_capacity(self) -> int:
        """The capacity ``_resize_table`` would grow to right now —
        what the spill-vs-grow decision budgets against, and what a
        failed growth's ``degrade`` event records as ``requested``."""
        cap = self._capacity
        while self._needs_growth_at(cap):
            cap *= 2
        return cap

    def _table_bytes(self, capacity: int) -> int:
        """Device bytes the visited table occupies at ``capacity``
        (the sharded engines multiply by the mesh)."""
        return capacity * 8

    # -- Tiered store hooks (stateright_tpu.store) -------------------------

    def _spill_enough(self, keep_fps: np.ndarray) -> bool:
        """Whether keeping only ``keep_fps`` device-resident satisfies
        the growth predicate at the CURRENT capacity (the spill
        target: evict just enough partitions that no growth is
        needed)."""
        return (len(keep_fps) + 2 * self._B_max * self._F
                <= self._capacity // 2)

    def _spill_seed(self, visited_fps: np.ndarray) -> np.ndarray:
        """Budget gate at table-build time (fresh runs and resumes): if
        seeding every fingerprint would size the table past the device
        budget, spill whole partitions to the warm tier first and seed
        only the survivors."""
        store = self._store
        if (not store.active or store.device_budget is None
                or not self._VISITED_SPILL_CAPABLE
                or not len(visited_fps)):
            return visited_fps
        visited_fps = np.asarray(visited_fps, np.uint64)

        def cap_for(n_rows: int) -> int:
            cap = self._capacity
            while cap < 4 * n_rows + 2 * self._B_max * self._F:
                cap *= 2
            return cap

        if self._table_bytes(cap_for(len(visited_fps))) \
                <= store.device_budget:
            return visited_fps
        mask = store.spill_mask(
            visited_fps,
            lambda keep: self._table_bytes(cap_for(len(keep)))
            <= store.device_budget)
        if not mask.any():
            return visited_fps
        store.spill_visited(visited_fps[mask])
        return visited_fps[~mask]

    def _spill_for_headroom(self) -> bool:
        """The spill-instead-of-grow arm of ``_grow_table``: when the
        table's next growth would exceed the device byte budget, evict
        whole ``fp % P`` partitions to the warm tier (membership stays
        covered by the per-wave host probe) and rebuild the table at
        the SAME capacity. Returns True when something spilled; when
        even a full eviction cannot restore headroom the device tier
        must exceed its budget and a ``pressure`` event records why."""
        store = self._store
        if (not store.active or store.device_budget is None
                or not self._VISITED_SPILL_CAPABLE):
            return False
        target = self._simulate_grow_capacity()
        if self._table_bytes(target) <= store.device_budget:
            return False  # normal growth stays inside the budget
        if not self._spill_enough(np.zeros(0, np.uint64)):
            # Even a fully-evicted table cannot satisfy the dispatch
            # headroom at this capacity: spilling would only buy
            # per-wave probe cost, not memory — the device tier must
            # exceed its budget (recorded, not fatal).
            store.note_device_pressure(self._table_bytes(target),
                                       store.device_budget)
            return False
        real = np.asarray(self._visited).reshape(-1)
        real = real[real != SENTINEL]
        mask = store.spill_mask(real, self._spill_enough)
        if not mask.any():
            store.note_device_pressure(self._table_bytes(target),
                                       store.device_budget)
            return False
        store.spill_visited(real[mask])
        self._visited = self._new_table(real[~mask])
        return True

    def _store_probe_fps(self, new_vecs: np.ndarray,
                         new_fps: np.ndarray) -> np.ndarray:
        """The fingerprints the spilled-partition membership probe
        keys on: the wave outputs PATH fingerprints, which under
        symmetry differ from the dedup (representative) fingerprints
        the table — and therefore the spilled partitions — hold, so
        the symmetric case recomputes them (one small jitted program
        per power-of-two block shape)."""
        if not self._use_symmetry:
            return new_fps
        k = len(new_vecs)
        if not k:
            return new_fps
        kb = 1 << max(0, (k - 1).bit_length())
        key = ("repfp", kb)
        fn = self._wave_cache.get(key)
        if fn is None:
            layout = self._wave_layout()
            dm = self._dm

            def rep_fp(vecs):
                if layout is not None:
                    vecs = layout.unpack(vecs)
                return device_fp64(jax.vmap(dm.representative)(vecs))

            fn = self._wave_cache[key] = jax.jit(rep_fp)
        pad = np.zeros((kb, new_vecs.shape[1]), np.uint32)
        pad[:k] = new_vecs
        return np.asarray(fn(jnp.asarray(pad)))[:k]

    def store_stats(self) -> dict:
        """The tiered store's occupancy/telemetry summary (also the
        ``scheduler_stats()["store"]`` payload and the Supervisor's
        abort high-water record)."""
        stats = self._store.stats()
        if self._store.active:
            with self._lock:
                resident = int(getattr(self, "_resident",
                                       self._unique_count))
                unique = self._unique_count
            stats["device"] = {
                "rows": resident,
                "table_bytes": self._table_bytes(self._capacity),
                "budget": self._store.device_budget,
            }
            stats["resident_ratio"] = round(
                resident / max(1, unique), 4)
        return stats

    def _degrade_bucket(self) -> bool:
        """OOM graceful degradation: drops the top rung of the batch
        bucket ladder — narrower dispatches need proportionally less
        table/arena headroom, so a failed growth is retried against a
        smaller requirement before the run gives up. Returns False when
        already at the narrowest rung (nothing left to shed)."""
        if len(self._buckets) <= 1:
            return False
        old = self._B_max
        self._buckets = self._buckets[:-1]
        self._B_max = self._buckets[-1]
        warnings.warn(
            f"table/arena growth hit an allocation failure; degrading "
            f"the dispatch bucket ladder {old} -> {self._B_max} and "
            "retrying", RuntimeWarning)
        if self._tracer.enabled:
            # requested/kept: the capacity the failed growth asked for
            # vs what actually exists — postmortems need to see WHY
            # memory ran out, not just that a bucket was shed.
            self._tracer.event(
                "degrade", kind="batch_bucket", old=old,
                new=self._B_max,
                requested=int(getattr(self, "_grow_requested", 0)),
                kept=int(self._capacity), _flush=True)
        return True

    def _handle_grow_failure(self, e: BaseException) -> None:
        """The shared OOM-degrade arm for every engine's growth site
        (call from the ``except`` clause): a non-OOM failure, or an OOM
        with nothing left to shed, re-raises; otherwise the ladder is
        degraded and one paired ``recover`` event is emitted — the lint
        pairs fault->recover 1:1 in stream order, and each caught
        OOM here pairs with exactly one fault/real-OOM."""
        if not is_oom(e) or not self._degrade_bucket():
            raise
        if self._tracer.enabled:
            self._tracer.event("recover", attempt=1, backoff_s=0.0,
                               resumed_from=None, kind="grow_degrade",
                               _flush=True)

    def _grow_table(self) -> None:
        """Growth with OOM graceful degradation: an allocation failure
        (real RESOURCE_EXHAUSTED/MemoryError, or the injected
        ``grow_oom`` fault) sheds the top batch bucket and retries; the
        smaller headroom requirement may even make the growth
        unnecessary. Only when the ladder is down to its base rung does
        the failure propagate (and the supervisor takes over)."""
        while True:
            try:
                self._grow_requested = self._simulate_grow_capacity()
                if self._faults.active:
                    self._faults.crash("grow_oom", self._tracer)
                # Tiered store: when the growth target would exceed the
                # device byte budget, evict cold visited partitions to
                # the warm tier instead (spill-instead-of-grow) — the
                # growth may then be unnecessary at this capacity.
                if self._spill_for_headroom() \
                        and not self._needs_growth():
                    return
                self._resize_table()
            except Exception as e:  # noqa: BLE001 — non-OOM re-raised
                self._handle_grow_failure(e)
                if self._needs_growth():
                    continue
            return

    def _resize_table(self) -> None:
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
            # A failed allocation must leave capacity describing the
            # table that actually exists, or the degrade-retry path
            # would dispatch against a phantom size.
            self._capacity = old
            raise

    # -- Path reconstruction (bfs.rs:314-342) ----------------------------

    def _fingerprint_state(self, state) -> int:
        return host_fp64(np.asarray(self._dm.encode(state), np.uint32))

    def _parent_map(self) -> Dict[int, Optional[int]]:
        """Materializes fingerprint -> parent fingerprint from the per-wave
        parent log (built lazily: the hot loop only appends arrays)."""
        with self._lock:
            log = self._parent_log
            while self._parents_consumed < len(log):
                child_fps, parent_fps = log[self._parents_consumed]
                if parent_fps is None:
                    for f in child_fps:
                        self._parents.setdefault(int(f), None)
                else:
                    for f, p in zip(child_fps.tolist(), parent_fps.tolist()):
                        self._parents.setdefault(f, p)
                # The dict now owns this block; drop the arrays.
                log[self._parents_consumed] = None
                self._parents_consumed += 1
        return self._parents

    def _reconstruct_path(self, fp: int) -> Path:
        parents = self._parent_map()
        fingerprints: deque = deque()
        next_fp = fp
        while next_fp in parents:
            source = parents[next_fp]
            fingerprints.appendleft(next_fp)
            if source is None:
                break
            next_fp = source
        return Path.from_fingerprints(
            self._model, fingerprints, fingerprint_fn=self._fingerprint_state)

    # -- Checker API -----------------------------------------------------

    def model(self) -> Model:
        return self._model

    def state_count(self) -> int:
        with self._lock:
            return self._state_count

    def unique_state_count(self) -> int:
        with self._lock:
            return self._unique_count

    def discoveries(self) -> Dict[str, Path]:
        with self._lock:
            found = list(self._discoveries.items())
        return {name: self._reconstruct_path(fp) for name, fp in found}

    def preempt(self) -> None:
        """Requests a cooperative stop: the wave loop drains any
        in-flight dispatch at its next boundary, writes the end-of-run
        checkpoint (when ``checkpoint_path`` is set — a safe point, so
        the image is a valid resume source), and stops with
        ``self.preempted`` True. The run is NOT failed: ``join()``
        returns normally and a later run resumes from the checkpoint
        bit-identically. Idempotent; a no-op once the run finished.
        (The classic sharded engine does not poll the flag; the job
        service schedules only onto the classic and fused engines.)"""
        self._preempt_evt.set()

    def join(self) -> "TpuBfsChecker":
        self._thread.join()
        if self._error is not None:
            raise self._error
        return self

    def is_done(self) -> bool:
        return self._done.is_set()


def build_wave(dm: DeviceModel, batch_size: int, capacity: int,
               prop_fns=(), use_sym: bool = False,
               out_rows: Optional[int] = None, layout=None):
    """The single-device wave program (jitted): one BFS level expansion.

    Exposed as a standalone builder so the wave can be compiled and
    benchmarked without spawning a checker (see ``__graft_entry__``).
    Signature of the returned function::

        wave(vecs: uint32[B, W], valid: bool[B], visited: uint64[C])
          -> (conds, succ_count, cand_count, terminal, new_count,
              new_vecs, new_fps, new_parent, new_mask, overflow,
              merged_visited)

    ``visited`` is donated (the table is updated in place on device).

    ``out_rows`` (default B*F) is the successor ladder's output rung:
    ``new_vecs``/``new_fps``/``new_parent`` carry only the first
    ``out_rows`` compacted novel rows, so small-novel-set waves skip
    most of the full-width compaction gather and output traffic. The
    full novelty mask ``new_mask`` and the device-computed ``overflow``
    flag (``new_count > out_rows``) are always emitted, so an
    overflowed wave is recovered losslessly by ``build_regather`` —
    the table insertions are already complete and order-identical.

    ``layout`` (a :class:`~stateright_tpu.tpu.packing.PackedLayout`)
    switches the STORAGE row format: input ``vecs`` and output
    ``new_vecs`` are then packed ``uint32[.., Wp]`` rows, unpacked to
    real lanes at wave start and re-packed after compaction — compute
    (step, properties, fingerprints, symmetry) always runs on the exact
    unpacked registers, so results are layout-independent.
    """
    B, F, W = batch_size, dm.max_fanout, dm.state_width
    S = B * F
    K = S if out_rows is None else min(max(1, int(out_rows)), S)
    prop_fns = list(prop_fns)

    def wave(vecs, valid, visited):
        reg = vecs if layout is None else layout.unpack(vecs)
        conds = eval_properties(prop_fns, reg)
        succ_flat, sflat, succ_count, terminal = expand_frontier(
            dm, reg, valid)
        dedup_fps, path_fps, _ = fingerprint_successors(
            dm, succ_flat, sflat, use_sym)
        new_mask, new_count, cand_count, merged, _ = (
            dedup_and_insert_counted(dedup_fps, visited, capacity))
        # Compact new successors to the front, preserving (frontier
        # row, action) order — the host enqueue order of bfs.rs:262 —
        # and gather only the ladder's K rows (packing AFTER the
        # gather: only the K surviving rows pay the codec).
        comp = compaction_order(new_mask)[:K]
        new_vecs = succ_flat[comp]
        if layout is not None:
            new_vecs = layout.pack(new_vecs)
        new_fps = path_fps[comp]
        new_parent = (comp // F).astype(jnp.int32)
        overflow = new_count > K
        conds_out = [c for c in conds if c is not None]
        return (conds_out, succ_count, cand_count, terminal, new_count,
                new_vecs, new_fps, new_parent, new_mask, overflow,
                merged)

    return jax.jit(wave, donate_argnums=(2,))


def build_mux_wave(dm: DeviceModel, batch_size: int, capacity: int,
                   prop_fns=(), use_sym: bool = False,
                   max_jobs: int = 8, layout=None,
                   pack_on: bool = False):
    """The multi-tenant wave program (jitted): one BFS level expansion
    over a batch drawn from SEVERAL jobs' frontiers at once (round 16).

    Input rows carry a trailing tenant lane (``layout`` must be a
    :meth:`~stateright_tpu.tpu.packing.PackedLayout.with_tenant_lane`
    derivation; when ``pack_on`` is False the model part is raw
    ``uint32[W]`` registers and only the tenant word is appended).
    Signature of the returned function::

        mux_wave(vecs: uint32[B, Wr+1], valid: bool[B],
                 tag_fps: uint64[J], visited: uint64[C])
          -> (conds, terminal, seg_succ[J], seg_cand[J], seg_novel[J],
              new_count, new_vecs, new_fps, new_dedup, new_parent,
              merged_visited)

    ``visited`` is donated and SHARED between tenants: each tenant's
    dedup fingerprints are XORed with its 64-bit ``tag_fps`` slot mask
    before probing, so the one open-addressing table holds per-
    (tenant, state) entries and tenants never dedup against each other
    (the shared-table-with-attribution design of arXiv:1004.2772). Path
    fingerprints stay untagged — parent maps and discoveries read real
    state fingerprints; ``new_dedup`` returns the UNtagged dedup
    (representative) fingerprints of the novel rows so the host can
    keep each tenant's visited set for its checkpoint.

    Per-tenant stats come back as segment sums over the tenant lane
    (``seg_succ``/``seg_cand``/``seg_novel``, fixed ``J = max_jobs``
    slots), which is what splits the dispatch-log totals per job.

    Bit-identity with solo runs falls out of the same two properties
    the B-independence suite pins: ``first_occurrence_candidates``
    resolves intra-wave duplicates to the earliest row (tenant rows are
    assembled contiguously in each tenant's own queue order, and
    cross-tenant fps never collide by construction), and
    ``compaction_order`` is stable, so each tenant's novel rows come
    back in exactly the order its solo engine would have enqueued.

    No successor ladder and no multi-wave pipelining here:
    the output rung is always the full ``B*F`` (an overflow path would
    complicate the per-tenant split for no gain at multiplexing's
    target shape — many SMALL frontiers sharing one dispatch)."""
    B, F = batch_size, dm.max_fanout
    S = B * F
    J = int(max_jobs)
    prop_fns = list(prop_fns)
    if layout is None or layout.tenant_lane is None:
        raise ValueError("build_mux_wave needs a tenant-lane layout")

    def mux_wave(vecs, valid, tag_fps, visited):
        slots = jnp.clip(layout.tenant(vecs).astype(jnp.int32), 0,
                         J - 1)
        reg = (layout.unpack(vecs) if pack_on
               else vecs[..., :layout.packed_width - 1])
        conds = eval_properties(prop_fns, reg)
        succ_flat, sflat, _, terminal = expand_frontier(dm, reg, valid)
        if use_sym:
            dedup_raw = device_fp64(jax.vmap(dm.representative)(
                succ_flat))
            path_fps = device_fp64(succ_flat)
        else:
            dedup_raw = device_fp64(succ_flat)
            path_fps = dedup_raw
        flat_slots = jnp.repeat(slots, F)
        tagged = jnp.where(sflat, dedup_raw ^ tag_fps[flat_slots],
                           jnp.uint64(SENTINEL))
        candidate = first_occurrence_candidates(tagged)
        new_mask, new_count, merged = global_insert(
            tagged, candidate, visited, capacity)
        seg_succ = jax.ops.segment_sum(
            sflat.astype(jnp.int64), flat_slots, num_segments=J)
        seg_cand = jax.ops.segment_sum(
            candidate.astype(jnp.int32), flat_slots, num_segments=J)
        seg_novel = jax.ops.segment_sum(
            new_mask.astype(jnp.int32), flat_slots, num_segments=J)
        comp = compaction_order(new_mask)[:S]
        new_reg = succ_flat[comp]
        new_parent = (comp // F).astype(jnp.int32)
        new_slots = slots[new_parent]
        if pack_on:
            new_vecs = layout.pack_tenant(new_reg, new_slots)
        else:
            new_vecs = jnp.concatenate(
                [new_reg, new_slots[:, None].astype(jnp.uint32)],
                axis=-1)
        conds_out = [c for c in conds if c is not None]
        return (conds_out, terminal, seg_succ, seg_cand, seg_novel,
                new_count, new_vecs, path_fps[comp], dedup_raw[comp],
                new_parent, merged)

    return jax.jit(mux_wave, donate_argnums=(3,))


def build_regather(dm: DeviceModel, batch_size: int, out_rows: int,
                   use_sym: bool = False, layout=None):
    """The successor ladder's overflow recovery (jitted, pure): re-runs
    the deterministic expand + fingerprint of the SAME batch and
    compacts with the wave's own novelty mask at a rung that fits::

        regather(vecs: uint32[B, W], valid: bool[B], new_mask: bool[B*F])
          -> (new_vecs, new_fps, new_parent)

    No table access and no novelty decisions happen here — the
    overflowed wave already inserted every novel candidate — so the
    recovered rows are bit-identical to what a full-width wave would
    have emitted (the differential suite pins this). Property
    evaluation and the dedup fingerprints are dead code under XLA DCE:
    only ``path_fps`` and the gather survive."""
    F = dm.max_fanout
    K = min(max(1, int(out_rows)), batch_size * F)

    def regather(vecs, valid, new_mask):
        if layout is not None:
            vecs = layout.unpack(vecs)
        succ_flat, sflat, _, _ = expand_frontier(dm, vecs, valid)
        _, path_fps, _ = fingerprint_successors(dm, succ_flat, sflat,
                                             use_sym)
        comp = compaction_order(new_mask)[:K]
        new_vecs = succ_flat[comp]
        if layout is not None:
            new_vecs = layout.pack(new_vecs)
        return new_vecs, path_fps[comp], (comp // F).astype(
            jnp.int32)

    return jax.jit(regather)


# -- Wave building blocks (shared with the sharded engine) ----------------
#
# Each stage runs under a ``jax.named_scope`` (``properties``, ``expand``,
# ``fingerprint``, ``local_dedup``, ``probe``); the fused wave body adds
# ``load`` and ``store``. The names land in every compiled program's op
# metadata, so a device trace attributes each op to its stage
# (``benchmark/trace_stages.py``); with no profiler running they cost
# nothing.

@jax.named_scope("properties")
def eval_properties(prop_fns, vecs):
    """Property predicates at "pop time" (bfs.rs:192-226); ``None`` slots
    are host-fallback properties."""
    return [None if fn is None else jax.vmap(fn)(vecs) for fn in prop_fns]


@jax.named_scope("expand")
def expand_frontier(dm: DeviceModel, vecs, valid):
    """Successor generation with boundary pruning (bfs.rs:231-244).

    Returns ``(succ_flat [B*F, W], valid_flat [B*F], succ_count,
    terminal [B])``; terminal rows have no in-boundary successor
    (bfs.rs:265-272).
    """
    has_boundary = dm.boundary(
        jnp.zeros((dm.state_width,), jnp.uint32)) is not None
    succ, sv = jax.vmap(dm.step)(vecs)
    sv = sv & valid[:, None]
    if has_boundary:
        sv = sv & jax.vmap(jax.vmap(dm.boundary))(succ)
    succ_count = jnp.sum(sv, dtype=jnp.int64)
    terminal = valid & ~sv.any(axis=1)
    s = sv.size
    return succ.reshape(s, dm.state_width), sv.reshape(s), succ_count, terminal


@jax.named_scope("fingerprint")
def fingerprint_successors(dm: DeviceModel, succ_flat, valid_flat,
                           use_sym: bool):
    """``(dedup_fps, path_fps, folded)``: under symmetry, dedup by the
    representative's fingerprint but continue paths with the original
    state's (the dfs.rs:258-267 rule). Invalid rows carry the sentinel.
    ``folded`` counts the valid successors whose representative is not
    the successor itself, those the group action moved (int32; None
    without symmetry)."""
    if use_sym:
        reps = jax.vmap(dm.representative)(succ_flat)
        dedup_fps = device_fp64(reps)
        path_fps = device_fp64(succ_flat)
        folded = jnp.sum(valid_flat & jnp.any(reps != succ_flat, axis=1),
                         dtype=jnp.int32)
    else:
        dedup_fps = device_fp64(succ_flat)
        path_fps = dedup_fps
        folded = None
    dedup_fps = jnp.where(valid_flat, dedup_fps, jnp.uint64(SENTINEL))
    return dedup_fps, path_fps, folded


def compaction_order(mask):
    """Indices that bring ``mask``'s True rows to the front, both halves
    in original order (what a stable argsort of ~mask computes, via two
    prefix sums instead of a sort)."""
    n = mask.shape[0]
    kept = jnp.cumsum(mask) - 1                 # target slot if True
    dropped = jnp.cumsum(~mask) - 1             # after all kept rows
    total_kept = kept[-1] + 1
    slot = jnp.where(mask, kept, total_kept + dropped)
    return (jnp.zeros((n,), jnp.int32).at[slot].set(
        jnp.arange(n, dtype=jnp.int32), mode="drop"))


#: Rows the fused waves' append loop carries per trip. A wave admits a
#: few percent of its B*F rows, and gathering, packing and writing a
#: row costs the same whether it is admitted or not; so the store
#: compacts the admitted rows' indices and appends them in chunks of
#: this many rows (fewer where a call has fewer rows). On a v5e the
#: store took 0.96 / 0.92 / 1.65 ms a wave in paxos-3 (~4,000 rows
#: admitted a wave) and 1.68 / 1.86 / 1.89 ms in 2pc-10 (~8,500) at
#: 4096 / 8192 / 16384-row chunks; the whole waves at 4096 and 8192
#: came within 0.8% of each other, the one faster in each cell. On the
#: 2x2 mesh (2pc-11, ~10,000 rows admitted an owner a round) the store
#: took 1.95 / 2.19 ms at 4096 / 16384, the waves within 0.3%.
APPEND_CHUNK = 8192


def append_chunk(rows: int) -> int:
    """The rows each trip of the append loop carries for a call over
    ``rows`` rows."""
    return min(rows, APPEND_CHUNK)


def append_window(rows: int) -> int:
    """Arena rows past the tail that appending up to ``rows`` rows may
    write: whole chunks. ``dynamic_update_slice`` moves a window that
    would overrun the arena back over admitted rows, so a wave runs only
    where this many rows are free."""
    chunk = append_chunk(rows)
    return -(-rows // chunk) * chunk


def gather_rows(rows, idx):
    """``rows[idx]`` for a 2-D ``rows``, one gather per column. Where a
    chunk of whole rows is appended to an arena as it was gathered, the
    TPU compiler lays the arena out as the gather's output and copies it
    (32 GiB for a 2^26-row arena of 13 words); stacked columns leave the
    arena's layout alone."""
    return jnp.stack([rows[:, w][idx] for w in range(rows.shape[1])],
                     axis=-1)


def append_rows(arena, tail, new_mask, take, acc):
    """Appends the rows ``new_mask`` marks to the ``arena`` arrays at
    ``tail``, in row order (the bfs.rs:262 enqueue order), walking them
    in chunks of ``append_chunk`` rows, so that the cost follows the
    admitted rows and not the wave's.

    ``take(idx, live, acc)`` gives one chunk: ``idx`` are the int32
    source indices of its rows, ``live`` marks those that are admitted
    (the rest are written past the new tail, where no read looks). It
    returns the chunk's rows, one array per arena array in the arena's
    row format, and ``acc`` with the live rows folded in. Returns
    ``(arena, acc, trips)``; ``trips`` (int32) is the chunks written."""
    n = new_mask.shape[0]
    chunk = append_chunk(n)
    padded = -(-n // chunk) * chunk
    # Compact: admitted row r's index goes to slot rank[r]. One int32
    # cumsum and one int32 scatter (the probe compacts its candidates
    # the same way).
    rank = jnp.cumsum(new_mask, dtype=jnp.int32) - 1
    count = rank[-1] + 1
    order = jnp.zeros((padded,), jnp.int32).at[
        jnp.where(new_mask, rank, padded)].set(
            jnp.arange(n, dtype=jnp.int32), mode="drop")
    lanes = jnp.arange(chunk, dtype=jnp.int32)

    def cond(carry):
        _, start, _, _ = carry
        return start < count

    def body(carry):
        arena, start, acc, trips = carry
        idx = jax.lax.dynamic_slice(order, (start,), (chunk,))
        rows, acc = take(idx, start + lanes < count, acc)
        at = tail + start.astype(tail.dtype)
        zero = jnp.zeros((), tail.dtype)
        arena = tuple(
            jax.lax.dynamic_update_slice(a, r, (at,) + (zero,) * (a.ndim - 1))
            for a, r in zip(arena, rows))
        return arena, start + chunk, acc, trips + 1

    arena, _, acc, trips = jax.lax.while_loop(
        cond, body, (tuple(arena), jnp.int32(0), acc, jnp.int32(0)))
    return arena, acc, trips


# Fibonacci mixing constant (2^64 / golden ratio). The *high* bits of
# fp * MIX index the table: under the sharded engine a shard only holds
# fingerprints with a fixed residue mod n_shards, so low bits of fp are
# correlated — the multiply-shift decorrelates the slot from them.
_TABLE_MIX = 0x9E3779B97F4A7C15
# Second mixer for the double-hashing step. The probe sequence is
# home + i*step (step odd, so it tours the whole power-of-two table):
# the while_loop in dedup_and_insert runs for the LONGEST chain among
# all candidates, and linear probing's clusters make that tail long —
# per-key step sequences keep the max chain near the O(log n / log log n)
# balls-in-bins bound instead.
_STEP_MIX = 0xC2B2AE3D27D4EB4F


def _probe_step_host(fps: np.ndarray, capacity: int) -> np.ndarray:
    shift = np.uint64(64 - (capacity.bit_length() - 1))
    with np.errstate(over="ignore"):
        step = ((fps.astype(np.uint64) * np.uint64(_STEP_MIX)) >> shift)
    return (step.astype(np.int64) | 1)


def host_table_insert(table: np.ndarray, fps: np.ndarray) -> None:
    """Inserts fingerprints into a host copy of the open-addressing table
    (vectorized double-hash probing, same slot/step functions as the
    device loop). Any table the host builds this way is a valid probe
    structure for the device: lookup walks the key's own probe sequence
    until the key or a SENTINEL gap. Used for seeding and for growth
    rehashes, where a scalar loop would stall the hot path for seconds
    per doubling."""
    if not len(fps):
        return
    capacity = len(table)
    mask = np.int64(capacity - 1)
    shift = np.uint64(64 - (capacity.bit_length() - 1))
    with np.errstate(over="ignore"):
        idx = ((fps.astype(np.uint64) * np.uint64(_TABLE_MIX))
               >> shift).astype(np.int64)
    step = _probe_step_host(fps, capacity)
    pending = np.ones(len(fps), bool)
    while pending.any():
        cur = table[idx]
        found = pending & (cur == fps)
        empty = pending & (cur == SENTINEL)
        # Claim: numpy fancy-store picks one winner per contended slot;
        # the re-gather tells the losers to advance (same as on device).
        table[idx[empty]] = fps[empty]
        won = empty & (table[idx] == fps)
        pending &= ~(found | won)
        idx = np.where(pending, (idx + step) & mask, idx)


def first_occurrence_candidates(dedup_fps):
    """Intra-wave dedup: True at the EARLIEST frontier-order occurrence
    of each non-sentinel fingerprint, preserving the host BFS enqueue
    order of bfs.rs:262.

    Sort-free: a fingerprint's scratch slot is a function of the
    fingerprint alone, so same-fp candidates always collide — a
    scatter-min of the row index resolves one whole fp group per
    contended slot per round (the group containing the slot's smallest
    row; its smallest row is the first occurrence), and unresolved
    groups advance by their fp-derived odd step. The globally smallest
    pending row always wins its slot, so each round retires at least
    one group. Replaced a stable u64 argsort that was ~70% of the
    dedup stage on the XLA CPU backend (22k-row waves: 5.9 of 8.4 ms).
    """
    return first_occurrence_counted(dedup_fps)[0]


@jax.named_scope("local_dedup")
def first_occurrence_counted(dedup_fps):
    """``first_occurrence_candidates`` plus its loop's round count
    (int32): ``(first, rounds)``."""
    return first_occurrence_unscoped(dedup_fps)


def first_occurrence_unscoped(dedup_fps):
    """``first_occurrence_counted`` outside the ``local_dedup`` scope,
    for a caller whose scope names the pass: the sharded wave's sender
    side runs it under ``exchange``, so that each wave has one
    ``local_dedup`` loop, the owner's."""
    n = dedup_fps.shape[0]
    m = 1 << max(int(n - 1).bit_length() + 1, 4)  # >= 2n, power of two
    shift = jnp.uint64(64 - (m.bit_length() - 1))
    h0 = ((dedup_fps * jnp.uint64(_TABLE_MIX)) >> shift).astype(jnp.int32)
    step = (((dedup_fps * jnp.uint64(_STEP_MIX)) >> shift)
            .astype(jnp.int32) | 1)  # odd: tours the power-of-two scratch
    rows = jnp.arange(n, dtype=jnp.int32)
    pending0 = dedup_fps != jnp.uint64(SENTINEL)

    def cond(carry):
        _, pending, _, _ = carry
        return pending.any()

    def body(carry):
        h, pending, first, rounds = carry
        scratch = jnp.full((m,), n, jnp.int32).at[
            jnp.where(pending, h, m)].min(rows, mode="drop")
        winner_row = scratch[h]
        winner_fp = dedup_fps[jnp.minimum(winner_row, n - 1)]
        same = pending & (winner_fp == dedup_fps)
        first = first | (same & (winner_row == rows))
        pending = pending & ~same
        h = jnp.where(pending, (h + step) & (m - 1), h)
        return h, pending, first, rounds + 1

    _, _, first, rounds = jax.lax.while_loop(
        cond, body, (h0, pending0, jnp.zeros((n,), bool), jnp.int32(0)))
    return first, rounds


def dedup_and_insert(dedup_fps, visited, capacity: int):
    """First-occurrence + insert-or-test against the open-addressing
    table: the two-level composition of ``first_occurrence_candidates``
    (intra-wave local dedup) and ``global_insert`` (the table probe).
    Returns ``(new_mask, new_count, visited)``. The table rehash
    programs use it."""
    candidate = first_occurrence_candidates(dedup_fps)
    return global_insert(dedup_fps, candidate, visited, capacity)


def dedup_and_insert_counted(dedup_fps, visited, capacity: int):
    """Both dedup levels of a wave program: the intra-wave local
    collapse (``first_occurrence_counted``) first, then the global
    probe (``global_insert_counted``) over the distinct survivors only.
    Returns ``(new_mask, new_count, cand_count, merged, rounds)``:
    ``cand_count`` (how many candidates reached the global probe) is
    the collapse-ratio telemetry, and ``rounds`` the int32 scalars
    ``(local dedup rounds, probe rounds)`` of the two loops."""
    candidate, local_rounds = first_occurrence_counted(dedup_fps)
    cand_count = jnp.sum(candidate, dtype=jnp.int32)
    new_mask, new_count, merged, probe_rounds = global_insert_counted(
        dedup_fps, candidate, visited, capacity)
    return (new_mask, new_count, cand_count, merged,
            (local_rounds, probe_rounds))


def global_insert(dedup_fps, candidate, visited, capacity: int):
    """Insert-or-test of pre-deduplicated candidates against the
    open-addressing table.

    ``candidate`` marks the rows that probe (exactly one per distinct
    non-sentinel fingerprint — the first occurrence — so the
    while_loop's longest-chain cost and the claim contention are paid
    once per distinct candidate, never per duplicate). Each candidate
    gathers its slot; if the slot holds the key it is a revisit; if
    empty, claim it with a scatter and re-gather to see who won (two
    DISTINCT candidates can race for one slot — XLA picks one winner,
    the loser advances). The loop runs until every candidate resolves;
    with load factor <= 1/2 (guaranteed by ``_grow_table``) probe
    chains are O(1) expected, so the per-wave cost never depends on
    table occupancy."""
    new_mask, new_count, visited, _ = global_insert_counted(
        dedup_fps, candidate, visited, capacity)
    return new_mask, new_count, visited


#: Rows the probe loop carries per trip. A round's gathers and claim
#: scatter cost per row they carry, candidate or not, and after local
#: dedup a few percent of a wave's rows are candidates; so the probe
#: packs the candidates densely and walks them in chunks of this many
#: rows (fewer where a call has fewer rows). One round over a 2^27-slot
#: table on a v5e took 38.49 ms at 212,992 rows and 1.68 / 2.05 / 2.76
#: / 4.17 ms at 2048 / 4096 / 8192 / 16384: smaller chunks pay a fixed
#: cost per round, and 16384-row chunks made the probe slower in every
#: benchmark cell than 8192-row ones.
PROBE_CHUNK = 8192


def probe_chunk(rows: int) -> int:
    """The rows each trip of the probe loop carries for a call over
    ``rows`` rows."""
    return min(rows, PROBE_CHUNK)


@jax.named_scope("probe")
def global_insert_counted(dedup_fps, candidate, visited, capacity: int):
    """``global_insert`` plus its loop's round count (int32):
    ``(new_mask, new_count, visited, rounds)``, the round loop's trips
    summed over the chunks, each over ``probe_chunk`` rows.

    The candidates are compacted to the front in row order and probed
    in chunks of ``probe_chunk`` rows, one chunk after another, so a
    later chunk sees the earlier ones' claims. Candidates are distinct
    fingerprints, so whether a row is new depends only on whether its
    key is in the table: ``new_mask`` and the table as a set are what
    a single loop over every row gives; only the slot a new key takes
    can differ."""
    sentinel = jnp.uint64(SENTINEL)
    n = dedup_fps.shape[0]
    chunk = probe_chunk(n)
    padded = -(-n // chunk) * chunk

    # Compact: candidate r's row index goes to slot rank[r]. One int32
    # scatter: scattering the u64 fingerprints themselves cost 25 ms
    # more a wave on a v5e in the twopc10-check cell.
    rank = jnp.cumsum(candidate, dtype=jnp.int32) - 1
    cand_count = rank[-1] + 1
    rows = jnp.full((padded,), n, jnp.int32).at[
        jnp.where(candidate, rank, padded)].set(
            jnp.arange(n, dtype=jnp.int32), mode="drop")

    shift = jnp.uint64(64 - (capacity.bit_length() - 1))
    slot_mask = jnp.int32(capacity - 1)
    lanes = jnp.arange(chunk, dtype=jnp.int32)

    def probe(table, chunk_rows, pending, rounds):
        fps = dedup_fps[jnp.minimum(chunk_rows, n - 1)]
        idx0 = ((fps * jnp.uint64(_TABLE_MIX)) >> shift).astype(jnp.int32)
        step = (((fps * jnp.uint64(_STEP_MIX)) >> shift)
                .astype(jnp.int32) | 1)  # odd: tours the power-of-two table

        def cond(carry):
            _, _, pending, _, _ = carry
            return pending.any()

        def body(carry):
            table, idx, pending, is_new, rounds = carry
            cur = table[idx]
            found = pending & (cur == fps)
            empty = pending & (cur == sentinel)
            # Claim attempt: scatter into empty home slots (out-of-bounds
            # rows drop); the re-gather reveals which candidate won a
            # contended slot.
            table = table.at[jnp.where(empty, idx, capacity)].set(
                fps, mode="drop")
            won = empty & (table[idx] == fps)
            is_new = is_new | won
            pending = pending & ~(found | won)
            idx = jnp.where(pending, (idx + step) & slot_mask, idx)
            return table, idx, pending, is_new, rounds + 1

        table, _, _, is_new, rounds = jax.lax.while_loop(
            cond, body, (table, idx0, pending, jnp.zeros((chunk,), bool),
                         rounds))
        return table, is_new, rounds

    def chunk_cond(carry):
        _, start, _, _ = carry
        return start < cand_count

    def chunk_body(carry):
        table, start, new_mask, rounds = carry
        chunk_rows = jax.lax.dynamic_slice(rows, (start,), (chunk,))
        # Rows past the last candidate start out resolved.
        table, is_new, rounds = probe(
            table, chunk_rows, start + lanes < cand_count, rounds)
        # Back to row order: the chunk's new rows, by their row index.
        new_mask = new_mask.at[jnp.where(is_new, chunk_rows, n)].set(
            True, mode="drop")
        return table, start + chunk, new_mask, rounds

    visited, _, new_mask, rounds = jax.lax.while_loop(
        chunk_cond, chunk_body,
        (visited, jnp.int32(0), jnp.zeros((n,), bool), jnp.int32(0)))
    return new_mask, jnp.sum(new_mask, dtype=jnp.int32), visited, rounds
