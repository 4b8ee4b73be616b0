"""The versioned run-telemetry event schema.

One schema, every producer: the four device engines, the host BFS/DFS
checkers, ``profiling.py`` and ``bench.py`` all emit events that
validate against the definitions here, so a single trace file
(``STpu_TRACE=path``, JSONL) can be linted (``tools/trace_lint.py``),
exported to a Perfetto-loadable Chrome trace
or a Prometheus text dump (``tools/trace_export.py``), and diffed across
rounds without per-engine parsers.

Every event carries a ``type`` key: ``run_start``, ``wave``,
``span``, ``counter``, ``gauge``, ``grow``, ``overflow_redispatch``,
``run_end``, .... The tracer stamps every one with ``schema_version``,
``engine``, ``run`` (a per-tracer id, so interleaved producers in one
file separate cleanly), and ``t`` (``time.monotonic()`` seconds).

The WAVE event is the load-bearing one: every engine emits the exact
same field set (``WAVE_FIELDS``) per dispatch, with ``null`` for fields
an engine genuinely has no value for (e.g. the host engines have no
device hash table, so ``load_factor`` is ``null`` — but the KEY is
present; consumers never need per-engine schemas). The cross-engine
suite in ``tests/test_obs_trace.py`` pins this.

This module is dependency-free (no jax, no numpy) on purpose: the lint
tool and the tests import it without touching a backend.
"""

from __future__ import annotations

from typing import Dict, List

__all__ = [
    "SCHEMA_VERSION", "TRACE_ENV", "EVENT_TYPES", "ENGINE_IDS",
    "SHED_REASONS",
    "WAVE_FIELDS", "WAVE_FIELDS_V1", "WAVE_FIELDS_V2",
    "WAVE_FIELDS_V5", "WAVE_FIELDS_V6", "WAVE_FIELDS_V8",
    "WAVE_FIELDS_V9", "WAVE_FIELDS_V11", "WAVE_FIELDS_V12",
    "WAVE_FIELDS_V14", "WAVE_FIELDS_V15", "WAVE_FIELDS_V16",
    "WAVE_FIELDS_V17", "WAVE_NULL_DEFAULTS", "validate_event",
    "validate_line",
]

#: v14: the closed vocabulary a ``shed`` event's ``reason`` must come
#: from — lives HERE (not in service/control.py) so the jax-free
#: consumers (``tools/trace_lint.py``) can validate it without pulling
#: the service package: ``slo_burn`` (admission gate engaged, priority
#: below the protected floor), ``brownout`` (the ladder raised the
#: floor over this priority), ``retry_budget`` (per-tenant token
#: bucket empty), ``queue_full`` (the bounded queue itself overflowed).
SHED_REASONS = ("slo_burn", "brownout", "retry_budget", "queue_full")

#: Bump on any field addition/removal/retyping; consumers gate on it.
#: v2 (round 9): wave events gained the packed-arena bandwidth gauges
#: ``bytes_per_state`` / ``arena_bytes`` / ``table_bytes``. v3 (round
#: 10): the resilience event family — ``fault`` (an ``STpu_FAULTS``
#: injection fired), ``recover`` (a supervised retry or in-engine
#: degradation recovered the run), ``degrade`` (graceful capability
#: reduction, e.g. the OOM batch-bucket halving), and terminal
#: ``abort`` (supervision exhausted its retries); wave fields are
#: unchanged from v2. v4 (round 11): the membership/elasticity family
#: — ``worker_lost`` (a heartbeat lease lapsed or a worker socket
#: died), ``migrate_done`` (a lost worker's partitions were rebuilt on
#: a survivor from their per-shard checkpoint generations),
#: ``rebalance`` (a joining worker received migrated partitions at a
#: drained barrier), and ``retry`` (one Supervisor retry record —
#: attempt index, jittered backoff, resume source); plus the
#: ``elastic`` coordinator as a wave-event producer. Wave fields are
#: unchanged from v2. v5 (round 12): distributed observability — wave
#: events gained the attribution keys ``worker`` (the elastic worker
#: that did the work), ``seq`` (the worker's per-process emission
#: sequence — the collector's merge/ordering key), ``epoch`` (the
#: ownership epoch the wave ran under), and ``round`` (the coordinated
#: round index); all four are ``null`` outside the elastic runtime.
#: New producers/events: ``elastic_worker`` (per-worker wave streams,
#: relayed to the coordinator and merged by ``obs/collect.py``),
#: ``straggler`` (the coordinator's per-round attribution record:
#: slowest worker, barrier wait-time share, per-worker segment
#: timings), and ``postmortem`` (the flight-recorder dump header —
#: ``obs/flight.py`` writes one per ring dump, followed by the
#: recorded events). ``retry``/``abort``/``worker_lost`` may carry an
#: optional ``dump`` rider naming the postmortem file. v6 (round 13):
#: the tiered-state-store family — wave events gained the per-tier
#: occupancy gauges ``tier_device_rows`` / ``tier_device_bytes`` /
#: ``tier_host_rows`` / ``tier_host_bytes`` / ``tier_disk_rows`` /
#: ``tier_disk_bytes`` (``null`` when the store is disarmed); new
#: event types ``spill`` (rows moved down a tier), ``page_in`` (a
#: paged-out frontier block came back ahead of dispatch), and
#: ``pressure`` (a tier crossed or reset against its byte budget —
#: the lint's monotonicity window marker). The host checkers and the
#: elastic runtime also stopped emitting permanent nulls for
#: ``capacity``/``load_factor``/``out_rows`` (real host-store
#: occupancy gauges; trace_lint enforces this for v6+ captures).
#: v7 (round 14): the job-service family (checking as a service) —
#: ``job_submit`` (a job entered the service queue: ``job`` id, the
#: corpus ``model`` name, the selected ``engine``), ``job_done`` (the
#: job ran to completion; carries its final cumulative counters), and
#: ``job_abort`` (the job left the service without completing —
#: preempted by ``DELETE /jobs/<id>``, failed past supervision, or
#: rejected; ``reason`` says which). ``tools/trace_lint.py`` asserts
#: every ``job_submit`` is eventually followed by a ``job_done`` or
#: ``job_abort`` for the SAME job id — a stream that ends with a job
#: neither finished nor acknowledged lost work. Wave fields are
#: unchanged from v6; the ``service`` meta-producer emits the family.
#: v8 (round 15): the single-kernel wave — wave events gained
#: ``kernel_path`` (which successor-path implementation the dispatch
#: ran: ``megakernel`` / ``interpret`` / ``pallas_probe`` / ``xla``;
#: ``null`` on producers with no device kernel, i.e. the host checkers
#: and the elastic coordinator) and ``rows`` (valid frontier rows the
#: dispatch consumed — with ``bucket`` x ``waves`` this yields kernel
#: occupancy, the figure megakernel A/Bs are judged against; ``null``
#: where not tracked). Wave fields are otherwise unchanged from v6.
#: v9 (round 16): cross-job wave multiplexing — wave events gained the
#: per-job attribution keys ``job_id`` (which service job the counted
#: work belongs to; ``null`` on solo-engine waves and on a mux wave's
#: TOTAL line) and ``jobs_in_wave`` (how many tenants shared the
#: dispatch; ``null`` outside the multiplexer). A mux group emits one
#: job_id-``null`` total per dispatch followed by exactly
#: ``jobs_in_wave`` job-attributed wave events whose
#: successors/candidates/novel sum to the total's —
#: ``tools/trace_lint.py`` enforces the split. New ``mux`` wave-event
#: producer (the shared group engine).
#: v10 (round 17): asynchronous host I/O — wave events gained
#: ``io_stall_s`` (seconds the wave loop spent blocked on host I/O
#: since the previous wave event: safe-point joins on the background
#: writer plus any synchronous write time; ``null`` where not
#: tracked). New event types ``ckpt_begin`` (a checkpoint
#: generation's snapshot was captured at a safe point and its write
#: started — possibly on the writer thread) and ``ckpt_done`` (that
#: generation landed durably). ``tools/trace_lint.py`` asserts every
#: ``ckpt_begin`` is eventually paired with a ``ckpt_done`` — or
#: explained by a ``fault``/``abort`` (a write that died mid-flight
#: surfaces at the next safe point) — and that a run's summed
#: ``io_stall_s`` fits inside its ``run_end`` duration window.
#: v11 (round 18): service-level observability — no wave-field
#: changes; three new event types. ``hist_snapshot`` carries one
#: producer's deterministic latency histograms (``obs/hist.py``:
#: fixed power-of-two buckets, cumulative-since-run-start counts) at a
#: bounded cadence — ``hists`` maps Prometheus-style series keys
#: (``name{label="v"}``) to ``{"buckets", "sum", "count"}`` and
#: ``snap`` is the producer's emission ordinal.
#: ``tools/trace_lint.py`` asserts per (run, series): bucket counts
#: sum to ``count``, and ``count``/``sum`` never decrease across
#: snapshots (``snap`` strictly increases per run). ``slo_breach``
#: records an objective's healthy->breaching transition
#: (``obs/slo.py``: rolling error-budget windows; edge-triggered).
#: ``anomaly`` records one slow-wave verdict from the online
#: per-program-key EWMA+MAD detector (``obs/anomaly.py``), with the
#: ``cause`` attributed from gauges already on the wave stream:
#: ``compile`` / ``io_stall`` / ``straggler`` / ``spill`` /
#: ``unknown``. Elastic workers relay their snapshots through the v5
#: relay machinery, so they merge causally like wave events; flight-
#: recorder dumps append the producer's final snapshot.
#: v12 (round 19): MXU-shaped successor generation — wave events
#: gained ``expand_impl`` (which expand-stage implementation the
#: dispatch's wave program embeds: ``matmul`` — the compiled
#: transition-table form — or ``step``, the vmapped ``DeviceModel.
#: step``; ``null`` on producers without a device wave). The v8
#: ``kernel_path`` values gained ``+matmul``-suffixed variants
#: (``xla+matmul`` / ``megakernel+matmul`` / ``interpret+matmul`` /
#: ``pallas_probe+matmul``) — the expand swap composes with every
#: kernel gate, and the recorded path must be the executed path on
#: both axes. The static per-row MAC count rides as a ``matmul_ops``
#: gauge event at run start when the plan is active.
#: v13 (round 20): the continuous wave profiler (``obs/prof.py``) —
#: wave events gained the cost-attribution keys ``cost_flops`` /
#: ``cost_bytes`` (the executed program's static XLA cost model:
#: ``cost_analysis()`` flops and bytes accessed, captured once at
#: compile and stamped on every dispatch; ``null`` when the profiler
#: is disarmed or the program never AOT-compiled) and ``cost_ratio``
#: (sampled dispatches only: measured wave seconds normalized by the
#: program's own first sampled baseline — finite by construction,
#: 1.0 at baseline; ``null`` on unsampled dispatches). New event type
#: ``profile_snapshot``: one sampled dispatch's roofline gauges —
#: achieved flops/s, bytes/s, arithmetic intensity, peak-memory
#: estimate — keyed by the canonical program key; ``snap`` is the
#: producer's sample ordinal (strictly increasing per run, like the
#: v11 hist ordinal). The v11 ``anomaly`` cause vocabulary gained
#: ``cost_model`` (a program drifting from its own cost-normalized
#: history). Elastic workers relay their snapshots through the v5
#: relay machinery like hist snapshots.
#: v14 (round 21): closed-loop overload control (service/control.py)
#: — no wave-field changes; five new event types. ``admit`` records
#: one submission the controller let through while the admission gate
#: was engaged (pressure was on but the job's priority cleared the
#: shed threshold); ``shed`` records one submission rejected at the
#: door (HTTP 429) — it ALWAYS carries a machine-readable ``reason``
#: (``slo_burn`` / ``queue_full`` / ``retry_budget`` / ``brownout``)
#: and the ``retry_after_s`` the client was told, computed from the
#: observed drain rate. ``park`` records the controller preempting a
#: running job to protect an at-risk deadline (the job is
#: checkpointed, never lost); ``resume`` records the parked job's
#: automatic resubmission (``resumed_as`` is the continuation job id).
#: ``tools/trace_lint.py`` asserts every ``park`` is eventually
#: followed by a ``resume`` or a terminal ``job_abort`` for the SAME
#: job id. ``controller`` records one brownout-ladder transition —
#: edge-triggered (consecutive events must change ``rung``), with
#: round-10 ``requested``/``kept`` honesty: ``requested`` is the rung
#: the policy asked for, ``kept`` the rung actually in force after
#: actuation.
#: v15: fused dispatches count their loops — wave events gained
#: ``probe_rounds`` and ``dedup_rounds`` (the rounds the visited-table
#: probe loop and the local-dedup loop ran, summed over the dispatch's
#: waves) and ``host_s`` (the host loop's own seconds on the dispatch:
#: its launch and processing, less the blocking stats read). ``null``
#: on producers that do not count them (every engine but the fused
#: one on its XLA path). The fused host loop's spans (``fused.launch``,
#: ``fused.process``, ``fused.stats_wait``, ``fused.grow``,
#: ``fused.checkpoint``, ``fused.parent_sync``) are ordinary ``span``
#: events when tracing is on.
#: v16: sharded-fused dispatches count their shard exchange — wave
#: events gained ``exchange_rows`` (successor rows the dispatch's waves
#: sent to another shard, summed over the mesh) and ``exchange_slots``
#: (the rows the all-to-alls carried between shards, padding
#: included). ``null`` on producers without an exchange. The
#: sharded-fused engine fills the v15 keys and spans too.
#: v17: fused dispatches count the rows their probe loop carried —
#: wave events gained ``probe_slots`` (each probe round's rows, summed
#: over the dispatch's waves; on a mesh the slowest shard's per wave).
#: Σ``candidates`` / Σ``probe_slots`` is how full the probe's rounds
#: run. ``null`` where ``probe_rounds`` is.
#: v18: sharded-fused dispatches send their exchange in rounds of
#: balanced buckets — wave events gained ``exchange_rounds`` (the
#: rounds every shard ran, summed over the dispatch's waves; at least
#: one a wave), and ``exchange_slots`` counts the rows those rounds
#: carried between shards. ``null`` where ``exchange_slots`` is.
#: v1-v17 streams still validate (against their version's field set);
#: streams NEWER than this validator are rejected with a clear
#: upgrade message instead of a cascade of field-set mismatches.
SCHEMA_VERSION = 18

#: Environment knob: set to a file path to stream JSONL events there.
#: Unset means the null tracer — the hot loop pays one attribute check.
TRACE_ENV = "STpu_TRACE"

#: Producers that emit wave events (``engine`` field values). Spans and
#: counters may additionally come from the meta-producers below.
#: ``elastic`` is the multi-worker coordinator (one wave event per
#: coordinated round, plus the membership lifecycle events);
#: ``elastic_worker`` is one elastic worker's relayed stream (schema
#: v5 — per-worker wave events, merged into the coordinator's file by
#: ``obs/collect.py``).
#: ``flight`` is the dump-time stamp on ring-buffer events whose
#: producer ran untraced (``obs/flight.py``) — postmortem files are
#: full citizens of the schema.
#: ``mux`` is the cross-job wave multiplexer (service/mux.py) — one
#: shared engine whose dispatches batch several jobs' frontiers.
ENGINE_IDS = ("classic", "fused", "sharded", "sharded_fused",
              "host_bfs", "host_dfs", "elastic", "elastic_worker",
              "flight", "mux")

#: Non-engine producers sharing the stream (spans/counters/resilience
#: events only). ``supervisor`` emits recover/abort, ``faults`` is the
#: injection registry's fallback producer for sites without an engine
#: tracer (the checkpoint writer, the bench device child).
#: ``service`` is the multi-tenant job service (stateright_tpu.service)
#: — it emits the v7 job lifecycle family into each job's trace.
META_PRODUCERS = ("profiling", "bench", "explorer", "supervisor",
                  "faults", "service")

_NULL = type(None)
_INT = (int,)            # bool is excluded explicitly in _typecheck
_NUM = (int, float)
_STR = (str,)
_BOOL = (bool,)

#: The per-dispatch wave event: field -> allowed types. EVERY engine
#: emits EVERY key. Count fields are per-dispatch deltas except
#: ``states``/``unique`` (cumulative, so a truncated trace still ends
#: on the right totals).
WAVE_FIELDS: Dict[str, tuple] = {
    "type": _STR,                  # == "wave"
    "schema_version": _INT,
    "engine": _STR,                # one of ENGINE_IDS
    "run": _STR,                   # tracer id: one checker run
    "wave": _INT,                  # dispatch index within the run
    "t": _NUM,                     # monotonic seconds at processing
    "states": _INT,                # cumulative generated states
    "unique": _INT,                # cumulative unique states
    "bucket": _INT,                # dispatch batch width B
    "waves": _INT,                 # BFS levels in this dispatch (fused >1)
    "inflight": _INT,              # pipeline depth at launch
    "compiled": _BOOL,             # interval carried a lazy XLA compile
    "successors": _INT,            # valid successors generated (delta)
    "candidates": _INT,            # distinct candidates probed (delta)
    "novel": _INT,                 # new unique states appended (delta)
    "out_rows": _INT + (_NULL,),   # successor-ladder rung K (null: n/a)
    "capacity": _INT + (_NULL,),   # visited-table capacity (null: host)
    "load_factor": _NUM + (_NULL,),  # occupancy/capacity after dispatch
    "overflow": _BOOL,             # dispatch paid an overflow regather
    # v2: packed-arena bandwidth gauges (ISSUE 4). bytes_per_state is
    # the STORED row width in bytes (packed when the model declares
    # lane_bits); arena/table bytes are device-resident footprints
    # (null where an engine has no such structure — host engines, or
    # the per-wave engines' host-side frontier).
    "bytes_per_state": _INT + (_NULL,),
    "arena_bytes": _INT + (_NULL,),
    "table_bytes": _INT + (_NULL,),
    # v5: distributed-attribution keys. ``null`` outside the elastic
    # runtime (the tracer stamps the defaults so no engine needs a
    # per-engine field set). ``seq`` is the worker's per-process
    # emission counter — it never resets across the migration tracer
    # rotation, so the collector's merge order and the lint's
    # per-worker monotonicity survive run-id rotation.
    "worker": _STR + (_NULL,),
    "seq": _INT + (_NULL,),
    "epoch": _INT + (_NULL,),
    "round": _INT + (_NULL,),
    # v6: tiered-state-store occupancy gauges (rows/bytes resident per
    # tier after the dispatch). ``null`` when the store is disarmed —
    # the tracer stamps the defaults, so no engine needs a per-engine
    # field set.
    "tier_device_rows": _INT + (_NULL,),
    "tier_device_bytes": _INT + (_NULL,),
    "tier_host_rows": _INT + (_NULL,),
    "tier_host_bytes": _INT + (_NULL,),
    "tier_disk_rows": _INT + (_NULL,),
    "tier_disk_bytes": _INT + (_NULL,),
    # v8: ``kernel_path`` names the successor path the dispatch ran,
    # "xla" (the op ladder) on every device engine; ``rows`` is the
    # valid frontier rows it consumed (occupancy numerator). Both
    # ``null`` on producers without a device wave.
    "kernel_path": _STR + (_NULL,),
    "rows": _INT + (_NULL,),
    # v9: cross-job multiplexing attribution. ``job_id`` names the
    # service job a per-job wave line belongs to (``null`` on solo
    # waves and on the mux total line); ``jobs_in_wave`` is the tenant
    # count of the shared dispatch (``null`` outside the multiplexer).
    "job_id": _STR + (_NULL,),
    "jobs_in_wave": _INT + (_NULL,),
    # v10: asynchronous host I/O. Seconds the wave loop spent blocked
    # on host I/O since the previous wave event (safe-point joins on
    # the background writer + synchronous write time). ``null`` where
    # not tracked (meta-producers, relayed historical streams).
    "io_stall_s": _NUM + (_NULL,),
    # v12: the expand stage the dispatch's wave program embeds, "step"
    # (the vmapped DeviceModel.step) on every device engine. ``null``
    # on producers without a device wave.
    "expand_impl": _STR + (_NULL,),
    # v13: continuous-profiler cost attribution (obs/prof.py). The
    # executed program's static XLA cost model (``null`` when the
    # profiler is disarmed, the producer has no compiled program, or
    # the program never AOT-compiled), and — on sampled dispatches
    # only — the measured-vs-own-baseline ``cost_ratio`` (finite by
    # construction; ``null`` on unsampled dispatches).
    "cost_flops": _NUM + (_NULL,),
    "cost_bytes": _NUM + (_NULL,),
    "cost_ratio": _NUM + (_NULL,),
    # v15: the fused dispatch's loop rounds and host seconds (null
    # where not counted).
    "probe_rounds": _INT + (_NULL,),
    "dedup_rounds": _INT + (_NULL,),
    "host_s": _NUM + (_NULL,),
    # v16: the sharded-fused dispatch's shard exchange (null where
    # there is none).
    "exchange_rows": _INT + (_NULL,),
    "exchange_slots": _INT + (_NULL,),
    # v17: the rows the probe's rounds carried (null where the rounds
    # are not counted).
    "probe_slots": _INT + (_NULL,),
    # v18: the shard exchange's rounds (null where there is none).
    "exchange_rounds": _INT + (_NULL,),
}

#: The wave keys a producer stamps ``null`` where its entry carries no
#: value, in stamping order — one list for the three stamping sites
#: (``RunTracer.wave``, ``RelayTracer.wave``, ``FlightRecorder``), so
#: no engine needs a per-engine field set. A relay stamps ``worker``
#: and ``seq`` itself.
WAVE_NULL_DEFAULTS = (
    # v5 attribution: null outside the elastic runtime.
    "worker", "seq", "epoch", "round",
    # v6 tier gauges: null outside a tiered-store run.
    "tier_device_rows", "tier_device_bytes",
    "tier_host_rows", "tier_host_bytes",
    "tier_disk_rows", "tier_disk_bytes",
    # v8 successor path and rows: null on producers without a device
    # wave (host checkers, elastic coordinator).
    "kernel_path", "rows",
    # v9 mux attribution: null on solo-engine waves.
    "job_id", "jobs_in_wave",
    # v10 async-I/O stall gauge: null where not tracked.
    "io_stall_s",
    # v12 expand stage: null on producers without a device wave.
    "expand_impl",
    # v13 cost attribution: null when the profiler is disarmed / the
    # program has no cost model / the dispatch was not sampled.
    "cost_flops", "cost_bytes", "cost_ratio",
    # v15 loop rounds and host seconds: null where not counted.
    "probe_rounds", "dedup_rounds", "host_s",
    # v16 shard-exchange counts: null on producers without an exchange.
    "exchange_rows", "exchange_slots",
    # v17 probe slots: null where the rounds are.
    "probe_slots",
    # v18 exchange rounds: null on producers without an exchange.
    "exchange_rounds",
)

#: v5 attribution keys (absent from v2-v4 wave events).
_WAVE_V5_KEYS = ("worker", "seq", "epoch", "round")

#: v6 tier gauges (absent from v1-v5 wave events).
_WAVE_V6_KEYS = ("tier_device_rows", "tier_device_bytes",
                 "tier_host_rows", "tier_host_bytes",
                 "tier_disk_rows", "tier_disk_bytes")

#: v8 single-kernel-wave keys (absent from v1-v7 wave events).
_WAVE_V8_KEYS = ("kernel_path", "rows")

#: v9 multiplexing keys (absent from v1-v8 wave events).
_WAVE_V9_KEYS = ("job_id", "jobs_in_wave")

#: v10 async-I/O keys (absent from v1-v9 wave events).
_WAVE_V10_KEYS = ("io_stall_s",)

#: v12 expand-stage attribution (absent from v1-v11 wave events).
_WAVE_V12_KEYS = ("expand_impl",)

#: v13 cost-attribution keys (absent from v1-v12 wave events).
_WAVE_V13_KEYS = ("cost_flops", "cost_bytes", "cost_ratio")

#: v15 loop-round and host-time keys (absent from v1-v14 wave events).
_WAVE_V15_KEYS = ("probe_rounds", "dedup_rounds", "host_s")

#: v16 shard-exchange keys (absent from v1-v15 wave events).
_WAVE_V16_KEYS = ("exchange_rows", "exchange_slots")

#: v17 probe-slot key (absent from v1-v16 wave events).
_WAVE_V17_KEYS = ("probe_slots",)

#: v18 exchange-round key (absent from v1-v17 wave events).
_WAVE_V18_KEYS = ("exchange_rounds",)

#: The v1 wave field set (no bandwidth gauges) — v1 captures validate
#: against this exactly.
WAVE_FIELDS_V1: Dict[str, tuple] = {
    k: v for k, v in WAVE_FIELDS.items()
    if k not in ("bytes_per_state", "arena_bytes", "table_bytes")
    + _WAVE_V5_KEYS + _WAVE_V6_KEYS + _WAVE_V8_KEYS + _WAVE_V9_KEYS
    + _WAVE_V10_KEYS + _WAVE_V12_KEYS + _WAVE_V13_KEYS
    + _WAVE_V15_KEYS + _WAVE_V16_KEYS + _WAVE_V17_KEYS + _WAVE_V18_KEYS}

#: The v2-v4 wave field set (bandwidth gauges, no attribution keys).
WAVE_FIELDS_V2: Dict[str, tuple] = {
    k: v for k, v in WAVE_FIELDS.items()
    if k not in _WAVE_V5_KEYS + _WAVE_V6_KEYS + _WAVE_V8_KEYS
    + _WAVE_V9_KEYS + _WAVE_V10_KEYS + _WAVE_V12_KEYS
    + _WAVE_V13_KEYS + _WAVE_V15_KEYS + _WAVE_V16_KEYS + _WAVE_V17_KEYS
    + _WAVE_V18_KEYS}

#: The v5 wave field set (attribution keys, no tier gauges).
WAVE_FIELDS_V5: Dict[str, tuple] = {
    k: v for k, v in WAVE_FIELDS.items()
    if k not in _WAVE_V6_KEYS + _WAVE_V8_KEYS + _WAVE_V9_KEYS
    + _WAVE_V10_KEYS + _WAVE_V12_KEYS + _WAVE_V13_KEYS + _WAVE_V15_KEYS
    + _WAVE_V16_KEYS + _WAVE_V17_KEYS + _WAVE_V18_KEYS}

#: The v6-v7 wave field set (tier gauges, no kernel-path keys).
WAVE_FIELDS_V6: Dict[str, tuple] = {
    k: v for k, v in WAVE_FIELDS.items()
    if k not in _WAVE_V8_KEYS + _WAVE_V9_KEYS + _WAVE_V10_KEYS
    + _WAVE_V12_KEYS + _WAVE_V13_KEYS + _WAVE_V15_KEYS + _WAVE_V16_KEYS
    + _WAVE_V17_KEYS + _WAVE_V18_KEYS}

#: The v8 wave field set (kernel-path keys, no mux attribution).
WAVE_FIELDS_V8: Dict[str, tuple] = {
    k: v for k, v in WAVE_FIELDS.items()
    if k not in _WAVE_V9_KEYS + _WAVE_V10_KEYS + _WAVE_V12_KEYS
    + _WAVE_V13_KEYS + _WAVE_V15_KEYS + _WAVE_V16_KEYS + _WAVE_V17_KEYS
    + _WAVE_V18_KEYS}

#: The v9 wave field set (mux attribution, no async-I/O gauge).
WAVE_FIELDS_V9: Dict[str, tuple] = {
    k: v for k, v in WAVE_FIELDS.items()
    if k not in _WAVE_V10_KEYS + _WAVE_V12_KEYS + _WAVE_V13_KEYS
    + _WAVE_V15_KEYS + _WAVE_V16_KEYS + _WAVE_V17_KEYS + _WAVE_V18_KEYS}

#: The v10-v11 wave field set (async-I/O gauge, no expand_impl).
WAVE_FIELDS_V11: Dict[str, tuple] = {
    k: v for k, v in WAVE_FIELDS.items()
    if k not in _WAVE_V12_KEYS + _WAVE_V13_KEYS + _WAVE_V15_KEYS
    + _WAVE_V16_KEYS + _WAVE_V17_KEYS + _WAVE_V18_KEYS}

#: The v12 wave field set (expand_impl, no cost attribution).
WAVE_FIELDS_V12: Dict[str, tuple] = {
    k: v for k, v in WAVE_FIELDS.items()
    if k not in _WAVE_V13_KEYS + _WAVE_V15_KEYS + _WAVE_V16_KEYS
    + _WAVE_V17_KEYS + _WAVE_V18_KEYS}

#: The v13-v14 wave field set (cost attribution, no loop rounds).
WAVE_FIELDS_V14: Dict[str, tuple] = {
    k: v for k, v in WAVE_FIELDS.items()
    if k not in _WAVE_V15_KEYS + _WAVE_V16_KEYS + _WAVE_V17_KEYS
    + _WAVE_V18_KEYS}

#: The v15 wave field set (loop rounds, no shard-exchange counts).
WAVE_FIELDS_V15: Dict[str, tuple] = {
    k: v for k, v in WAVE_FIELDS.items()
    if k not in _WAVE_V16_KEYS + _WAVE_V17_KEYS + _WAVE_V18_KEYS}

#: The v16 wave field set (shard-exchange counts, no probe slots).
WAVE_FIELDS_V16: Dict[str, tuple] = {
    k: v for k, v in WAVE_FIELDS.items()
    if k not in _WAVE_V17_KEYS + _WAVE_V18_KEYS}

#: The v17 wave field set (probe slots, no exchange rounds).
WAVE_FIELDS_V17: Dict[str, tuple] = {
    k: v for k, v in WAVE_FIELDS.items() if k not in _WAVE_V18_KEYS}

_WAVE_FIELDS_BY_VERSION = {1: WAVE_FIELDS_V1, 2: WAVE_FIELDS_V2,
                           3: WAVE_FIELDS_V2, 4: WAVE_FIELDS_V2,
                           5: WAVE_FIELDS_V5, 6: WAVE_FIELDS_V6,
                           7: WAVE_FIELDS_V6, 8: WAVE_FIELDS_V8,
                           9: WAVE_FIELDS_V9, 10: WAVE_FIELDS_V11,
                           # v11 added event types only; its wave
                           # field set matches v10.
                           11: WAVE_FIELDS_V11, 12: WAVE_FIELDS_V12,
                           # v14 added event types only; its wave
                           # field set matches v13.
                           13: WAVE_FIELDS_V14, 14: WAVE_FIELDS_V14,
                           15: WAVE_FIELDS_V15, 16: WAVE_FIELDS_V16,
                           17: WAVE_FIELDS_V17, 18: WAVE_FIELDS}

#: Required fields per trace event type (beyond the stamped
#: schema_version/engine/run/t, which every event carries).
EVENT_TYPES: Dict[str, Dict[str, tuple]] = {
    "run_start": {"unix_t": _NUM, "meta": (dict,)},
    "wave": {},  # checked field-exactly against WAVE_FIELDS instead
    "span": {"name": _STR, "dur": _NUM, "depth": _INT},
    "counter": {"name": _STR, "value": _NUM, "inc": _NUM},
    "gauge": {"name": _STR, "value": _NUM},
    "grow": {"kind": _STR, "old": _INT, "new": _INT},
    "overflow_redispatch": {"bucket": _INT, "out_rows": _INT,
                            "novel": _INT},
    "run_end": {"dur": _NUM, "counters": (dict,)},
    # v3: the resilience family. trace_lint additionally asserts every
    # fault is eventually followed by a recover or a terminal abort.
    "fault": {"point": _STR, "hit": _INT, "mode": _STR},
    "recover": {"attempt": _INT, "backoff_s": _NUM,
                "resumed_from": _STR + (_NULL,)},
    "degrade": {"kind": _STR, "old": _INT, "new": _INT},
    "abort": {"reason": _STR, "attempts": _INT},
    # v4: the membership/elasticity family. trace_lint additionally
    # asserts every worker_lost is eventually followed by a
    # migrate_done or a terminal abort (the membership invariant), and
    # counts retry like recover for the fault pairing.
    "worker_lost": {"worker": _STR, "epoch": _INT},
    "worker_join": {"worker": _STR, "epoch": _INT},
    "migrate_done": {"partitions": _INT, "to": _STR, "epoch": _INT},
    "rebalance": {"partitions": _INT, "to": _STR, "epoch": _INT},
    "retry": {"attempt": _INT, "backoff_s": _NUM, "jitter_s": _NUM,
              "resumed_from": _STR + (_NULL,)},
    # v5: the distributed-observability family. ``straggler`` is the
    # coordinator's per-round attribution record — ``workers`` maps
    # each worker to its segment timings ({compute_s, exchange_s,
    # wait_s, states_s, load_share}); ``wait_share`` is the fraction
    # of worker-time the round spent idle at the barrier.
    # ``postmortem`` heads a flight-recorder dump file (obs/flight.py)
    # and is followed by the ring's recorded events verbatim.
    "straggler": {"round": _INT, "epoch": _INT,
                  "slowest": _STR + (_NULL,), "wait_share": _NUM,
                  "workers": (dict,)},
    "postmortem": {"reason": _STR, "name": _STR, "events": _INT},
    # v6: the tiered-state-store family. ``spill`` records rows moving
    # DOWN a tier (``tier`` is the destination: "host" or "disk";
    # ``kind`` is what moved: "visited" / "frontier" / "arena_span"),
    # ``page_in`` a paged-out frontier block returning ahead of
    # dispatch, and ``pressure`` a tier crossing or resetting against
    # its byte budget (trace_lint's monotonicity window marker).
    "spill": {"tier": _STR, "kind": _STR, "rows": _INT, "bytes": _INT},
    "page_in": {"tier": _STR, "kind": _STR, "rows": _INT,
                "bytes": _INT},
    "pressure": {"tier": _STR, "used": _INT, "budget": _INT},
    # v7: the job-service family. ``job`` is the service-assigned job
    # id — the lint's pairing key (every submit eventually paired with
    # a done or abort for the SAME id). ``job_done`` carries the final
    # cumulative counters so a per-job summary never needs to fold the
    # wave stream; ``job_abort``'s reason distinguishes a preemption
    # (checkpointed, resumable) from a terminal failure.
    "job_submit": {"job": _STR, "model": _STR, "job_engine": _STR},
    "job_done": {"job": _STR, "states": _INT, "unique": _INT},
    "job_abort": {"job": _STR, "reason": _STR},
    # v10: the async-I/O checkpoint lifecycle. ``gen`` is the writer's
    # per-run generation counter (monotone; rotation keeps gen-1 as
    # ``.prev``); ``async`` records whether the write ran on the
    # background writer thread or inline. ``ckpt_done`` is emitted by
    # whichever thread finished the write — trace_lint pairs begin/done
    # oldest-first per run and lets a ``fault``/``abort`` explain a
    # begin whose write died mid-flight.
    "ckpt_begin": {"gen": _INT, "path": _STR, "async": _BOOL},
    "ckpt_done": {"gen": _INT, "path": _STR, "write_s": _NUM},
    # v11: the service-observability family. ``hist_snapshot`` is one
    # producer's cumulative latency histograms at a bounded cadence
    # (``hists``: series key -> {"buckets", "sum", "count"}; ``snap``:
    # the producer's emission ordinal — trace_lint asserts per-series
    # monotonicity and sum/count consistency). ``slo_breach`` is the
    # edge-triggered healthy->breaching transition of one rolling
    # error-budget objective. ``anomaly`` is one slow-wave verdict
    # with its attributed cause (compile / io_stall / straggler /
    # spill / unknown).
    "hist_snapshot": {"hists": (dict,), "snap": _INT},
    "slo_breach": {"objective": _STR, "target": _NUM, "burn": _NUM,
                   "window_s": _NUM, "good": _INT, "bad": _INT},
    # v13: the ``anomaly`` cause vocabulary additionally includes
    # ``cost_model`` (obs/anomaly.py — a program whose measured time
    # drifts from its own cost-normalized history).
    "anomaly": {"cause": _STR, "key": _STR, "dur_s": _NUM,
                "baseline_s": _NUM, "dev_s": _NUM},
    # v13: one sampled dispatch's roofline gauges (obs/prof.py).
    # ``key`` is the canonical program key the static cost record is
    # filed under; ``snap`` is the producer's sample ordinal (strictly
    # increasing per run — the lint invariant); ``measured_s`` the
    # rest-point-timed dispatch seconds; ``cost_ratio`` measured
    # seconds over the program's own first sampled baseline (finite by
    # construction). The flops/bytes gauges are ``null`` for programs
    # with no AOT cost analysis.
    "profile_snapshot": {"key": _STR, "kernel_path": _STR + (_NULL,),
                         "expand_impl": _STR + (_NULL,), "snap": _INT,
                         "measured_s": _NUM, "cost_ratio": _NUM,
                         "flops": _NUM + (_NULL,),
                         "bytes": _NUM + (_NULL,),
                         "peak_bytes": _INT + (_NULL,),
                         "flops_per_s": _NUM + (_NULL,),
                         "bytes_per_s": _NUM + (_NULL,),
                         "intensity": _NUM + (_NULL,)},
    # v14: the overload-control family (service/control.py). ``admit``
    # is one submission let through while the admission gate was
    # engaged; ``shed`` one rejected at the door — ``reason`` is
    # mandatory and machine-readable (slo_burn / queue_full /
    # retry_budget / brownout) and ``retry_after_s`` is what the 429
    # told the client, derived from the observed drain rate. ``park``
    # / ``resume`` bracket a controller preemption: the lint pairs
    # them by exact job id (a park not eventually resumed or
    # terminally aborted lost work). ``controller`` is one
    # brownout-ladder transition — edge-triggered per run (the rung
    # must change), with requested/kept honesty.
    "admit": {"job": _STR, "tenant": _STR, "priority": _INT,
              "queue_depth": _INT},
    "shed": {"tenant": _STR, "priority": _INT, "reason": _STR,
             "retry_after_s": _NUM},
    "park": {"job": _STR, "reason": _STR},
    "resume": {"job": _STR, "resumed_as": _STR},
    "controller": {"rung": _INT, "action": _STR, "requested": _INT,
                   "kept": _INT},
}

_STAMPED = {"type": _STR, "schema_version": _INT, "engine": _STR,
            "run": _STR, "t": _NUM}


def _typecheck(value, types) -> bool:
    # bool subclasses int: a field typed int/float must not accept True.
    if isinstance(value, bool):
        return bool in types
    return isinstance(value, tuple(t for t in types if t is not bool))


def _check_fields(obj: dict, fields: Dict[str, tuple],
                  where: str) -> List[str]:
    errors = []
    for name, types in fields.items():
        if name not in obj:
            errors.append(f"{where}: missing field {name!r}")
        elif not _typecheck(obj[name], types):
            errors.append(
                f"{where}: field {name!r} has type "
                f"{type(obj[name]).__name__}, expected "
                f"{'/'.join(t.__name__ for t in types)}")
    return errors


def validate_event(obj) -> List[str]:
    """Validates one decoded event; returns a list of error strings
    (empty = valid)."""
    if not isinstance(obj, dict):
        return ["event is not a JSON object"]
    etype = obj.get("type")
    where = f"trace event {etype!r}"
    if etype not in EVENT_TYPES:
        return [f"{where}: unknown type (expected one of "
                f"{sorted(EVENT_TYPES)})"]
    errors = _check_fields(obj, _STAMPED, where)
    ver = obj.get("schema_version")
    if isinstance(ver, int) and ver > SCHEMA_VERSION:
        # A capture from a NEWER build: one clear message, no cascade
        # of field-set mismatches the reader cannot act on.
        errors.append(
            f"{where}: schema_version {ver} is newer than this "
            f"validator ({SCHEMA_VERSION}); upgrade the tools to lint "
            "this capture")
        return errors
    if etype == "wave":
        # Older captures validate against THEIR version's exact field
        # set (v1 predates the bandwidth gauges).
        fields = _WAVE_FIELDS_BY_VERSION.get(
            ver if isinstance(ver, int) else SCHEMA_VERSION,
            WAVE_FIELDS)
        errors += _check_fields(obj, fields, where)
        extras = set(obj) - set(fields)
        if extras:
            # Exact field set: one schema for every engine, no
            # per-engine riders — additions go through a version bump.
            errors.append(f"{where}: unexpected fields "
                          f"{sorted(extras)}")
        if ("engine" in obj and obj.get("engine") not in ENGINE_IDS):
            errors.append(f"{where}: engine {obj.get('engine')!r} not in "
                          f"{ENGINE_IDS}")
    else:
        errors += _check_fields(obj, EVENT_TYPES[etype], where)
    return errors


def validate_line(line: str) -> List[str]:
    """Validates one raw JSONL line (blank lines are skipped)."""
    import json

    line = line.strip()
    if not line:
        return []
    try:
        obj = json.loads(line)
    except ValueError as e:
        return [f"invalid JSON: {e}"]
    return validate_event(obj)
