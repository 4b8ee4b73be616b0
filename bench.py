"""Benchmark: TPU-engine checking throughput vs the host BFS engine.

Prints ONE JSON line: ``{"metric", "value", "unit", "vs_baseline",
"device", ...}`` — ALWAYS, even on failure or timeout. A watchdog
*thread* (armed at ``BENCH_BUDGET_S`` minus a grace margin) emits the
line with whatever has been measured so far and exits 0, so a stage
that hangs still leaves a record. With no TPU (and no
``BENCH_PLATFORM=cpu``) the line carries the error, the value is 0 and
the exit code is 2.

The north-star metric (BASELINE.json) is states/sec on ``paxos check 3``
with property-violation parity vs ``spawn_bfs``. Stages, cheapest first,
each updating the result line as it lands:

1. The device: the bench runs in ONE process on the backend JAX
   resolves, and that backend must be a TPU. Without one it exits
   non-zero, unless ``BENCH_PLATFORM=cpu`` asks for a CPU rehearsal
   (whose numbers are labelled ``cpu``). No CPU number ever stands in
   for a chip result.
2. Parity gate + first rate sample on a FULL enumeration small enough to
   always finish: ``2pc check 5`` (8,832 states) — identical unique-state
   counts and discovery sets vs multithreaded ``spawn_bfs``
   (zero missed violations), plus a steady-state device rate, on the
   backend that produces the headline.
3. Host baseline on the north-star workload (``paxos check 3``), bounded
   by ``target_state_count`` so it yields a *rate* without full
   enumeration (the reference's analog metric is the ``sec=`` line of
   ``Checker::report``, `checker.rs:229-232`; its bench runs each example
   with all cores, `bench.sh:29-32`).
4. Device engine on the same bounded workload; the headline value is its
   steady-state throughput: the slope of (time, states) across waves
   excluding the first (compile-bearing) wave.

``vs_baseline`` is the ratio of the device steady-state rate to the
**compiled** host baseline on the same machine and workload: the native
C++ multithreaded BFS (``native/host_bfs.cc``, the reference's
`bfs.rs:17-342` engine design — the honest analog of the reference's
multithreaded Rust checker), run to completion on the full state space.
``vs_python_host`` reports the ratio against the Python ``spawn_bfs``
for continuity with rounds 1-3; when the native extension is
unavailable, ``vs_baseline`` falls back to that Python rate and the
metric string says so. The caps differ
by design (host: ``BENCH_HOST_CAP`` states for a quick rate sample;
device: ``BENCH_TPU_CAP`` so steady-state waves dominate) — both engines
expand the same BFS prefix of the same state space, and each engine's
rate is flat across that range, but the ratio is a throughput comparison,
not a same-work wall-clock race.

Env knobs:
  BENCH_BUDGET_S       total wall budget, watchdog fires ~20s before
                       (default 450)
  BENCH_WORKLOAD       paxos | 2pc            (default paxos)
  BENCH_CLIENTS        paxos client count     (default 3 — the north star)
  BENCH_LIVENESS       1 adds the "eventually chosen" Eventually property
                       (liveness via ebits)
  BENCH_SYMMETRY       1 dedups by the client-symmetry representative
                       (with BENCH_CLIENTS=4 + BENCH_LIVENESS=1 this is
                       BASELINE.json config 5; the native baseline
                       switches to the symmetry-capable compiled DFS)
  BENCH_PROF           1 arms the continuous wave profiler
                       (STpu_PROF=1) for every engine the bench spawns
                       — XLA cost-model capture per compiled program
                       plus sampled roofline timings. The headline
                       engine's final per-program gauges are hoisted
                       under RESULT["prof"] (prof.* keys, which
                       bench_compare diffs key by key and tolerates
                       one-sided). BENCH_PROF_SAMPLE overrides the
                       sampling cadence (default 32)
  BENCH_RESULT_OUT     path: also write the RESULT json to this file
                       (the driver's BENCH_r{N}.json) at emit time
  BENCH_COMPARE_BASELINE  path to the previous round's BENCH json: at
                       emit time run tools/bench_compare.py against
                       BENCH_RESULT_OUT with --max-regress
                       BENCH_MAX_REGRESS (default 20) and fold the
                       gate's status into the exit code
  BENCH_2PC_RMS        2pc RM count           (default 7)
  BENCH_HOST_CAP       host-baseline target_state_count (default 60000)
  BENCH_TPU_CAP        device-run target_state_count    (default 400000)
  BENCH_PARITY_RMS     2pc parity-gate RM count         (default 5)
  BENCH_ELASTIC_WORKERS  >0 routes the device stage through the elastic
                       multi-worker runtime (resilience/elastic.py)
                       with that many workers; the headline rate then
                       measures the coordinated sharded wave end to end
  BENCH_ELASTIC_PARTITIONS  logical shard count (default 8)
  BENCH_ELASTIC_BATCH  per-worker rows per coordinated round (default
                       512)
  BENCH_ELASTIC_TRANSPORT  thread (default) | process — process spawns
                       one OS process per worker (the multi-host
                       rehearsal; slower start on CPU boxes)
  BENCH_ELASTIC_KILL_ROUND  >0 kills the last worker just before that
                       coordinated round (migration drill: the RESULT
                       elastic block records the worker_lost ->
                       migrate_done cycle and the rate shows the dip)
  BENCH_ELASTIC_JOIN_ROUND  >0 admits one extra worker at that round
                       (rebalance drill)
  BENCH_SERVICE_JOBS   >0 adds the checking-as-a-service stage: submits
                       N concurrent small jobs to an in-process
                       JobService and reports jobs/s + the shared
                       wave-program cache hit ratio + cold-vs-warm job
                       latency under RESULT["service"]
  BENCH_SERVICE_WORKERS  service worker-pool width (default 2)
  BENCH_SERVICE_MODEL  corpus model the jobs check (default twopc)
  BENCH_SOAK_JOBS      >0 adds the sustained-traffic soak stage: ONE
                       arrival schedule of N same-shape jobs replayed
                       against a wave-multiplexed service and a
                       one-engine-each service (A/B on the same box);
                       aggregate jobs/s + p50/p99 job latency and the
                       per-job counter cross-check land under
                       RESULT["soak"]
  BENCH_SOAK_ARRIVAL   soak inter-arrival gap, seconds (default 0.05)
  BENCH_SOAK_MIX       preempt (default): inject one preempt->resume
                       into each soak arm so the latency tail includes
                       a drained-and-resumed job; steady: none
  BENCH_SOAK_TRACE     gen (or a tools/traffic_gen trace path) adds the
                       open-loop overload A/B: the SAME pre-sampled
                       arrival schedule replayed against an overload-
                       controller-armed service vs the disarmed
                       baseline; goodput, interactive deadline hit
                       rate/p99, sheds-by-reason and park/resume
                       counts land under RESULT["soak_trace"]
  BENCH_SOAK_TRACE_SEED / _DURATION / _RATE
                       trace generation knobs for gen (default 0/6s/
                       4Hz); _QUEUE bounds both arms' job queue
                       (default 16) so the disarmed arm's overflow
                       mode is reachable inside the bench budget;
                       _SLO overrides the STpu_SLO spec BOTH arms
                       observe under (the ON arm's burn signal;
                       default job_latency=1.0,queue_wait=0.3,
                       window=10)
  BENCH_PLATFORM       cpu runs a labelled CPU rehearsal; unset, the
                       bench needs a TPU and exits non-zero without one
  BENCH_TPU_BATCH      override the device batch size (the adaptive
                       scheduler's base bucket)
  BENCH_TPU_MAX_BATCH  top of the adaptive bucket ladder (default
                       16x the batch; the engine re-picks the dispatch
                       width per dispatch from the live frontier)
  STpu_TRACE           path: stream the round's run telemetry (engine
                       wave events + bench stage spans) as JSONL —
                       lint with tools/trace_lint.py, open in Perfetto via
                       tools/trace_export.py
"""

import json
import os
import sys
import threading
import time

_ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _ROOT)
sys.path.insert(0, os.path.join(_ROOT, "examples"))

_T0 = time.monotonic()
_BUDGET = float(os.environ.get("BENCH_BUDGET_S", "450"))
_EMITTED = threading.Event()

# The watchdog reads/replaces whole values; stages replace whole keys —
# no partial-update races worth locking over.
RESULT = {"metric": "tpu_bfs states/sec", "value": 0.0,
          "unit": "states/sec", "vs_baseline": 0.0}

#: parity-gate status; the single source for the metric's parity clause
#: and the machine-readable RESULT["parity_failed"] flag.
_PARITY = {"status": "pending"}
_HEADLINE = {}  # "recompose": closure re-rendering the headline metric


def _parity_clause() -> str:
    return {"pending": "parity gate pending",
            "ok": "parity gated on 2pc full enumeration",
            "failed": "PARITY GATE FAILED — see error"}[_PARITY["status"]]


def _remaining() -> float:
    return _BUDGET - (time.monotonic() - _T0)


def _emit_and_exit(code: int = 0) -> None:
    if not _EMITTED.is_set():
        _EMITTED.set()
        RESULT["bench_sec"] = round(time.monotonic() - _T0, 1)
        line = json.dumps(RESULT)
        print(line, flush=True)
        # Round-19 exit path: persist the RESULT dict and gate it
        # against the previous round's headline. Both steps are
        # best-effort — the printed line above is the contract; a
        # filesystem or comparison error must never eat it.
        out = os.environ.get("BENCH_RESULT_OUT")
        if out:
            try:
                with open(out, "w", encoding="utf-8") as f:
                    f.write(line + "\n")
            except OSError as e:
                print(f"BENCH_RESULT_OUT write failed: {e}",
                      file=sys.stderr, flush=True)
        baseline = os.environ.get("BENCH_COMPARE_BASELINE")
        if out and baseline:
            try:
                sys.path.insert(0, os.path.join(_ROOT, "tools"))
                from bench_compare import main as compare
                rc = compare([baseline, out, "--max-regress",
                              os.environ.get("BENCH_MAX_REGRESS", "20")])
                code = max(code, rc)
            except Exception as e:  # noqa: BLE001 — the gate is advisory
                print(f"bench_compare gate errored: {e}",
                      file=sys.stderr, flush=True)
    os._exit(code)


def _watchdog() -> None:
    grace = min(20.0, _BUDGET * 0.1)
    while True:
        left = _remaining() - grace
        if left <= 0:
            RESULT["error"] = (RESULT.get("error", "") +
                               "; watchdog fired at budget").lstrip("; ")
            _emit_and_exit(0)
        time.sleep(min(left, 5.0))


def _steady_rate(tpu) -> float:
    # Preferred: the engines' dispatch_log + compile_log. Compiles run
    # on the host thread between stats reads (AOT — see engine._aot),
    # so each compile's duration lies inside exactly one dispatch
    # interval; steady state is total states over total wall MINUS the
    # compile time inside the covered span (the adaptive scheduler's
    # bigger buckets compile mid-run, which the plain first-wave
    # exclusion below would mis-charge to throughput). Lazily-compiled
    # paths (no AOT) instead flag their interval via ``compiled`` and
    # are dropped whole.
    log = list(tpu.wave_log)
    dlog = list(getattr(tpu, "dispatch_log", ()) or ())
    clog = list(getattr(tpu, "compile_log", ()) or ())
    if dlog and log:
        # Global span: under pipelined dispatch a launch's execution can
        # complete inside an earlier interval, so per-interval slopes
        # misattribute; total-states over total-wall-minus-compiles is
        # robust to that (everything happened inside the span).
        t0 = log[0][0]
        t_last = dlog[-1]["t"]
        span_t = t_last - t0
        span_s = 0.0
        t_prev, s_prev = log[0]
        dropped = []  # intervals removed whole (lazy compiles inside)
        for e in dlog:
            if e.get("compiled"):
                # Lazily-compiled interval (no AOT timing): drop whole.
                span_t -= e["t"] - t_prev
                dropped.append((t_prev, e["t"]))
            else:
                span_s += e["states"] - s_prev
            t_prev, s_prev = e["t"], e["states"]
        for t_end, dur in clog:
            if t0 < t_end <= t_last and not any(
                    lo < t_end <= hi for lo, hi in dropped):
                span_t -= dur
        if span_t > 0 and span_s > 0:
            return span_s / span_t
    # Fallback: wave_log[0] is the run start; wave_log[1] ends the first
    # (compile-bearing) wave. Steady state is the slope over the rest.
    if not log:
        return 0.0
    if len(log) >= 3:
        (t1, s1), (t2, s2) = log[1], log[-1]
        return (s2 - s1) / max(t2 - t1, 1e-9)
    return (log[-1][1] - log[0][1]) / max(log[-1][0] - log[0][0], 1e-9)


def _host_bfs(model, cap=None):
    b = model.checker().threads(os.cpu_count() or 1)
    if cap:
        b = b.target_state_count(cap)
    t0 = time.monotonic()
    checker = b.spawn_bfs().join()
    sec = time.monotonic() - t0
    return checker, checker.state_count() / max(sec, 1e-9), sec


def _native_bfs_rate(model):
    """The honest baseline: the compiled multithreaded host BFS
    (native/host_bfs.cc — the reference's `bfs.rs:17-342` engine design
    in C++), run to completion or to BENCH_NATIVE_CAP generated states,
    whichever comes first (the rate is flat across that range; the
    `native_host_complete` field records which it was). Returns
    states/sec or None when the extension/model form is unavailable."""
    from stateright_tpu.native.host_bfs import HOSTBFS_AVAILABLE

    if not HOSTBFS_AVAILABLE:
        return None
    dm = model.device_model()
    if dm.native_form() is None:
        return None
    cap = int(os.environ.get("BENCH_NATIVE_CAP", "3000000"))
    b = model.checker().threads(os.cpu_count() or 1).target_state_count(cap)
    if os.environ.get("BENCH_SYMMETRY") == "1":
        # Keep the baseline apples-to-apples under config 5: the native
        # DFS is the symmetry-capable compiled engine.
        checker = b.symmetry().spawn_native_dfs(dm).join()
    else:
        checker = b.spawn_native_bfs(dm).join()
    rate = checker.state_count() / max(checker.seconds(), 1e-9)
    RESULT["native_host_states"] = checker.state_count()
    RESULT["native_host_sec"] = round(checker.seconds(), 3)
    RESULT["native_host_complete"] = checker.is_done()
    return rate


def _return_model(model):
    """Module-level identity factory: picklable for the elastic
    runtime's process-transport workers (each worker rebuilds its own
    DeviceModel from the model object)."""
    return model


def _elastic_bfs(model, workers, cap=None, deadline=None,
                 symmetry=False, checkpoint_path=None, resume_from=None,
                 chaos=True):
    """The device stage through the elastic multi-worker runtime
    (BENCH_ELASTIC_WORKERS): same (checker-like, rate, finished)
    contract as ``_tpu_bfs``, with the membership lifecycle recorded
    under RESULT["elastic"]. The kill/join drill knobs apply only with
    ``chaos`` (the headline run — the parity gate's elastic run stays
    unfaulted so it gates the wave, not the recovery). A chaos drill
    needs per-shard generations to migrate from, so a missing
    ``checkpoint_path`` gets a per-run scratch path, removed after."""
    import glob
    import tempfile
    from functools import partial

    from stateright_tpu.resilience.elastic import ElasticChecker

    kill_round = int(os.environ.get("BENCH_ELASTIC_KILL_ROUND", "0")) \
        if chaos else 0
    join_round = int(os.environ.get("BENCH_ELASTIC_JOIN_ROUND", "0")) \
        if chaos else 0
    own_ckpt = checkpoint_path is None and (kill_round or join_round)
    if own_ckpt:
        fd, checkpoint_path = tempfile.mkstemp(
            prefix="stpu_bench_elastic_", suffix=".npz")
        os.close(fd)
        os.unlink(checkpoint_path)
    try:
        run = ElasticChecker(
            partial(_return_model, model),
            workers=workers,
            n_partitions=int(os.environ.get("BENCH_ELASTIC_PARTITIONS",
                                            "8")),
            batch_rows=int(os.environ.get("BENCH_ELASTIC_BATCH", "512")),
            transport=os.environ.get("BENCH_ELASTIC_TRANSPORT",
                                     "thread"),
            checkpoint_path=checkpoint_path, resume_from=resume_from,
            symmetry=symmetry, target_state_count=cap,
            kill_at=({kill_round: f"w{workers - 1}"}
                     if kill_round else None),
            join_at=({join_round: f"w{workers}"}
                     if join_round else None))
        if deadline is None:
            run.join()
            finished = True
        else:
            while not run.is_done() and time.monotonic() < deadline:
                time.sleep(0.25)
            finished = run.is_done()
            if not finished:
                # Deadline cut: stop the coordinator at its next round
                # barrier BEFORE touching the scratch files it is
                # migrating from, and so its workers stop burning the
                # cores the remaining bench stages are about to
                # measure.
                run.stop()
                waited = time.monotonic() + 30.0
                while not run.is_done() and time.monotonic() < waited:
                    time.sleep(0.1)
        if run.is_done():
            try:
                # Reap the listener/acceptor; a stop()ped run returns
                # cleanly, an aborted one surfaces its stored error
                # here instead of silently reporting a rate.
                run.join()
            except Exception as e:  # noqa: BLE001 — partial rate stands
                RESULT["elastic_stage_error"] = \
                    f"{type(e).__name__}: {e}"[:300]
                finished = False  # an aborted run is not a clean finish
    finally:
        # Only sweep the scratch generations once the run has actually
        # stopped — deleting them under a coordinator mid-migration
        # would manufacture the very data loss the drill tests. A
        # still-running run past its stop grace leaks tempfiles
        # instead (and is recorded).
        if own_ckpt and ("run" not in locals() or run.is_done()):
            for stale in glob.glob(checkpoint_path + "*"):
                try:
                    os.unlink(stale)
                except OSError:
                    pass
        elif own_ckpt:
            RESULT["elastic_stage_error"] = (
                "elastic run did not stop within grace; scratch "
                f"checkpoints left at {checkpoint_path}")
    sched = run.scheduler_stats()
    stats = sched["elastic"]
    stats["events"] = [e["type"] for e in run.events]
    # Distributed-observability aggregates (round 12): per-worker
    # straggler gauges, merge counters, postmortem dump paths.
    obs = sched.get("elastic_obs", {})
    stats["obs"] = obs
    if chaos or "elastic" not in RESULT:
        # The parity gate's unfaulted elastic run must not clobber the
        # headline's kill/join drill record (accelerator stage order
        # runs the gate AFTER the headline).
        RESULT["elastic"] = stats
        # Straggler summary hoisted to top-level keys so BENCH_r12+
        # diffs read it without digging: the worst round's barrier
        # wait share and the slowest-worker histogram.
        RESULT["elastic_max_wait_share"] = obs.get("max_wait_share")
        RESULT["elastic_slowest_worker"] = obs.get("slowest", {})
        if kill_round or join_round:
            dumps = [p for p in obs.get("postmortems", [])
                     if os.path.exists(p)]
            RESULT["elastic_postmortems"] = dumps
            if kill_round and not dumps:
                # The drill's observability gate: a kill without a
                # flight-recorder postmortem means the always-on ring
                # failed its one job.
                RESULT["elastic_stage_error"] = (
                    "kill drill produced no flight-recorder "
                    "postmortem dump")
    return run, _steady_rate(run), finished


def _tpu_bfs(model, batch, table_capacity, cap=None, deadline=None,
             symmetry=None, max_batch=None, checkpoint_path=None,
             resume_from=None, elastic_chaos=True):
    """Runs the device engine; with a ``deadline`` (monotonic), polls
    instead of joining and returns the steady rate measured so far when
    time runs out — a partially-completed run still yields a valid rate
    (the wave_log holds per-wave samples). ``finished`` reports which.

    ``checkpoint_path``/``resume_from`` thread straight through to the
    engine (resilience subsystem).

    ``symmetry=None`` follows the BENCH_SYMMETRY knob (the headline);
    pass ``False`` to force it off — the parity gate must, because its
    host side counts raw states and the host/device symmetry partitions
    are intentionally different strengths (RewritePlan orbits vs the
    coarser device canonical form: 665 vs 314 on 2pc), so symmetric
    counts would never gate equal even with both engines correct.

    The fused engine is the fast path; if it fails on this backend
    (an engine bug would otherwise zero the whole bench), fall back to
    the classic per-wave engine once and record why."""
    if symmetry is None:
        symmetry = os.environ.get("BENCH_SYMMETRY") == "1"

    elastic_workers = int(os.environ.get("BENCH_ELASTIC_WORKERS", "0"))
    if elastic_workers:
        return _elastic_bfs(model, elastic_workers, cap=cap,
                            deadline=deadline, symmetry=symmetry,
                            checkpoint_path=checkpoint_path,
                            resume_from=resume_from,
                            chaos=elastic_chaos)

    def spawn(fused):
        b = model.checker()
        if cap:
            b = b.target_state_count(cap)
        if symmetry:
            # Driver config 5: dedup by the client-exchangeability
            # representative (register_workload.py sym section).
            b = b.symmetry()
        # Pre-size the fused engine's arena alongside the table so a
        # bounded run never recompiles mid-flight; max_batch_size arms
        # the adaptive bucket ladder (frontier-proportional widths).
        return b.spawn_tpu_bfs(
            batch_size=batch,
            max_batch_size=max_batch,
            table_capacity=table_capacity,
            arena_capacity=table_capacity // 2,
            checkpoint_path=checkpoint_path,
            checkpoint_every_waves=int(
                os.environ.get("BENCH_CKPT_EVERY", "64")),
            resume_from=resume_from,
            # Packed-arena A/B knob (round 9): unset = the engine's
            # backend-aware auto (packed on accelerators, unpacked on
            # the CPU fallback); 1/0 force either arm.
            pack_arena=(None if "BENCH_PACK_ARENA" not in os.environ
                        else os.environ["BENCH_PACK_ARENA"] != "0"),
            fused=fused)

    def run(checker):
        if deadline is None:
            checker.join()
            return checker, _steady_rate(checker), True
        while not checker.is_done() and time.monotonic() < deadline:
            time.sleep(0.25)
        finished = checker.is_done()
        if finished:
            checker.join()
        return checker, _steady_rate(checker), finished

    try:
        return run(spawn(fused=None))
    except Exception as e:  # noqa: BLE001 — salvage with the classic engine
        RESULT["fused_engine_error"] = f"{type(e).__name__}: {e}"[:300]
        return run(spawn(fused=False))


def _stage_parity_gate(platform):
    """Full-enumeration parity on 2pc (zero missed violations) + the
    round's first guaranteed device rate sample, on the backend that
    produces the headline."""
    from two_phase_commit import TwoPhaseSys

    if _PARITY["status"] == "ok":
        return  # already gated (e.g. before a late-resolved CPU headline)
    rms = int(os.environ.get("BENCH_PARITY_RMS", "5"))
    model = TwoPhaseSys(rms)
    host, host_rate, host_sec = _host_bfs(model)
    # Raw counts on both sides regardless of BENCH_SYMMETRY — see
    # _tpu_bfs's symmetry note. The gate never runs the elastic chaos
    # drills: it gates wave correctness, not recovery.
    tpu, tpu_rate, _ = _tpu_bfs(model, 1024, 1 << 16, symmetry=False,
                                elastic_chaos=False)
    assert tpu.unique_state_count() == host.unique_state_count(), (
        "unique-state mismatch: tpu=%d host=%d"
        % (tpu.unique_state_count(), host.unique_state_count()))
    assert set(tpu.discoveries()) == set(host.discoveries()), (
        "discovery mismatch: tpu=%s host=%s"
        % (sorted(tpu.discoveries()), sorted(host.discoveries())))
    _PARITY["status"] = "ok"
    RESULT["parity_backend"] = platform
    RESULT.update({
        "parity": f"2pc check {rms}: {host.unique_state_count()} unique, "
                  f"counts+discoveries identical ({platform} backend)",
        "parity_host_states_per_sec": round(host_rate, 1),
        "parity_tpu_states_per_sec": round(tpu_rate, 1),
    })
    if "tpu_states" not in RESULT:
        # No headline yet (CPU stage order): this rate is the fallback
        # result line until the headline stage replaces it.
        RESULT.update({
            "metric": f"tpu_bfs states/sec on {platform}, 2pc check {rms} "
                      f"(full enumeration, parity vs spawn_bfs OK)",
            "value": round(tpu_rate, 1),
            "vs_baseline": round(tpu_rate / max(host_rate, 1e-9), 3),
        })


def build_workload(platform):
    """Returns ``(model, name, batch, table, tpu_cap, max_batch)`` for
    the headline workload. ``max_batch`` tops the adaptive bucket
    ladder: the engine re-picks its dispatch width per dispatch from
    the live frontier, so the bulk of a wide run batches at
    ``max_batch`` while the seed/tail waves stay at ``batch``."""
    # On the 1-core CPU fallback, small batches win (cache-resident
    # waves); a real accelerator amortizes fixed per-wave cost over much
    # wider frontiers — and the fused engine's throughput wants a cap
    # big enough for several steady-state dispatches.
    wide = platform not in (None, "cpu")
    tpu_cap = int(os.environ.get("BENCH_TPU_CAP",
                                 "1500000" if wide else "400000"))
    if os.environ.get("BENCH_WORKLOAD", "paxos") == "paxos":
        from paxos import PaxosModelCfg

        clients = int(os.environ.get("BENCH_CLIENTS", "3"))
        liveness = os.environ.get("BENCH_LIVENESS") == "1"
        model = PaxosModelCfg(clients, 3, liveness=liveness).into_model()
        name, batch, table = (
            f"paxos check {clients}"
            + (" +liveness" if liveness else "")
            + (" +sym" if os.environ.get("BENCH_SYMMETRY") == "1"
               else ""),
            4096 if wide else 1024,
            1 << 22 if wide else 1 << 20)
    else:
        from two_phase_commit import TwoPhaseSys

        rms = int(os.environ.get("BENCH_2PC_RMS", "7"))
        model = TwoPhaseSys(rms)
        name, batch, table = (f"2pc check {rms}",
                              8192 if wide else 2048,
                              1 << 22 if wide else 1 << 20)
    batch = int(os.environ.get("BENCH_TPU_BATCH", str(batch)))
    max_batch = int(os.environ.get("BENCH_TPU_MAX_BATCH",
                                   str(batch * 16)))
    return model, name, batch, table, tpu_cap, max_batch


def _hoist_succ_telemetry(scheduler: dict) -> None:
    """Copies the successor-path (ISSUE 2) and packed-arena (ISSUE 4)
    telemetry to top-level result keys so a round's K-rung usage,
    overflow-redispatch count, collapse ratio, bytes-per-state, and
    arena/table byte high-water marks are one grep away."""
    if not isinstance(scheduler, dict):
        return
    if scheduler.get("succ_ladder") is not None:
        RESULT["succ_ladder"] = scheduler["succ_ladder"]
    if scheduler.get("local_dedup") is not None:
        RESULT["local_dedup"] = scheduler["local_dedup"]
    packing = scheduler.get("packing")
    if isinstance(packing, dict):
        RESULT["packing"] = packing
        RESULT["bytes_per_state"] = packing.get("bytes_per_state")
        RESULT["arena_bytes_high_water"] = \
            packing.get("arena_bytes_high_water")
        RESULT["table_bytes_high_water"] = \
            packing.get("table_bytes_high_water")
    store = scheduler.get("store")
    if isinstance(store, dict) and store.get("enabled"):
        # Tiered-store telemetry (ISSUE 8): the graceful-degradation
        # record, one grep away.
        RESULT["tier_store"] = store
        RESULT["tier_spill_bytes"] = store.get("spill_bytes")
        RESULT["tier_resident_ratio"] = store.get("resident_ratio")
    pr = scheduler.get("prof")
    if isinstance(pr, dict):
        # Continuous profiler (ISSUE 18, BENCH_PROF=1): the headline
        # engine's per-program roofline gauges, numeric fields only so
        # they flatten to comparable prof.* keys in bench_compare.
        hoisted = {"dispatches": pr.get("dispatches"),
                   "sampled": pr.get("sampled")}
        for key, snap in (pr.get("programs") or {}).items():
            hoisted[key] = {
                f: snap[f] for f in ("flops", "bytes", "flops_per_s",
                                     "bytes_per_s", "intensity",
                                     "cost_ratio", "measured_s")
                if isinstance(snap.get(f), (int, float))}
        RESULT["prof"] = hoisted


def _stage_tier_drill(platform):
    """The memory-pressure arm of the kill-drill family
    (``BENCH_TIER_DRILL=1``): run a small 2pc enumeration with a device
    arena/table capped far below the state-space size (forcing visited
    spills through warm to cold) and GATE on the run finishing with
    totals and discoveries bit-identical to an uncapped run. Fills
    ``RESULT["tier_drill"]``; a mismatch sets ``parity_failed``."""
    import tempfile

    from two_phase_commit import TwoPhaseSys

    rms = int(os.environ.get("BENCH_TIER_DRILL_RMS", "4"))
    model = TwoPhaseSys(rms)

    def run(**tier):
        c = model.checker().spawn_tpu_bfs(
            batch_size=32, table_capacity=1024, fused=False, **tier)
        c.join()
        return c

    # The clean reference must be GENUINELY uncapped: main() maps
    # BENCH_TIER_* onto the STpu_TIER_* env knobs before the stages
    # run, and a kwarg-less engine would arm the store off that env —
    # turning the gate into capped-vs-capped. Strip the knobs for the
    # reference run only.
    from stateright_tpu.store.tiered import (TIER_DEVICE_ENV,
                                             TIER_DIR_ENV,
                                             TIER_HOST_ENV)

    saved = {var: os.environ.pop(var, None)
             for var in (TIER_DEVICE_ENV, TIER_HOST_ENV, TIER_DIR_ENV)}
    try:
        clean = run()
    finally:
        for var, val in saved.items():
            if val is not None:
                os.environ[var] = val
    want = (clean.state_count(), clean.unique_state_count(),
            tuple(sorted(clean.discoveries())))
    seg_dir = (os.environ.get("BENCH_TIER_DIR")
               or tempfile.mkdtemp(prefix="stpu-tier-drill-"))
    capped = run(tier_device_bytes=40_000, tier_host_bytes=4096,
                 tier_dir=seg_dir)
    got = (capped.state_count(), capped.unique_state_count(),
           tuple(sorted(capped.discoveries())))
    stats = capped.store_stats()
    RESULT["tier_drill"] = {
        "rms": rms, "match": got == want,
        "states": got[0], "unique": got[1],
        "spills": stats["spills"],
        "spill_bytes": stats["spill_bytes"],
        "disk_rows": stats["disk"]["rows"],
        "probe_hits": stats["probe_hits"],
        "resident_ratio": stats["resident_ratio"],
    }
    if got != want:
        _PARITY["status"] = "failed"
        RESULT["parity_failed"] = True
        raise AssertionError(
            f"tier drill mismatch: capped {got} vs clean {want}")
    if not stats["spill_bytes"]:
        raise AssertionError(
            "tier drill never spilled — the caps no longer exercise "
            "the store; tighten BENCH_TIER knobs")


def _stage_async_io(platform):
    """The async-host-I/O A/B arm (``BENCH_ASYNC_IO=1``): interleaved
    knob-on/knob-off runs of a checkpoint-heavy 2pc config (generation
    every 4 waves — well under the checkpoint_every_waves<=8 bar) plus
    one spill-capped tiered pair, reporting the wave-loop I/O stall
    share per arm and GATING on counters/discoveries/final-generation
    BYTES being identical across arms. Fills ``RESULT["async_io"]``; a
    mismatch sets ``parity_failed``."""
    import hashlib
    import tempfile

    from two_phase_commit import TwoPhaseSys

    rms = int(os.environ.get("BENCH_ASYNC_IO_RMS", "4"))
    reps = int(os.environ.get("BENCH_ASYNC_IO_REPS", "3"))
    model = TwoPhaseSys(rms)
    work = tempfile.mkdtemp(prefix="stpu-async-io-")

    def run(arm, async_io, **tier):
        path = os.path.join(work, f"{arm}.ckpt")
        for stale in (path, path + ".prev"):
            if os.path.exists(stale):
                os.remove(stale)
        t0 = time.monotonic()
        c = model.checker().spawn_tpu_bfs(
            batch_size=32, table_capacity=2048, fused=False,
            async_io=async_io, checkpoint_path=path,
            checkpoint_every_waves=4, **tier)
        c.join()
        wall = time.monotonic() - t0
        stats = c.scheduler_stats()["async_io"]
        # The stall the wave loop actually ate: inline write seconds
        # when sync (every write blocks the loop), join-wait seconds
        # when async (only the residue the overlap failed to hide).
        stall = stats["join_wait_s"] if async_io else stats["busy_s"]
        digest = hashlib.sha256(open(path, "rb").read()).hexdigest()
        ident = (c.state_count(), c.unique_state_count(),
                 tuple(sorted(c.discoveries())), digest)
        return ident, wall, stall, stats

    def ab_pair(label, **tier):
        walls = {True: [], False: []}
        stalls = {True: [], False: []}
        idents = {}
        overlap = 0.0
        # Interleaved (on, off, on, off, ...): both arms sample the
        # same thermal/cache drift — the 2-core-box noise discipline
        # every A/B in this bench follows.
        for _ in range(max(1, reps)):
            for async_io in (True, False):
                ident, wall, stall, stats = run(
                    f"{label}-{'on' if async_io else 'off'}",
                    async_io, **tier)
                walls[async_io].append(wall)
                stalls[async_io].append(stall)
                prev = idents.setdefault(async_io, ident)
                if prev != ident:
                    raise AssertionError(
                        f"{label}: non-deterministic arm "
                        f"(async_io={async_io})")
                overlap = max(overlap, stats.get("overlap_s", 0.0))
        if idents[True] != idents[False]:
            _PARITY["status"] = "failed"
            RESULT["parity_failed"] = True
            raise AssertionError(
                f"async_io {label} mismatch: on={idents[True][:3]} "
                f"off={idents[False][:3]} ckpt_sha "
                f"on={idents[True][3][:12]} off={idents[False][3][:12]}")
        row = {}
        for async_io in (True, False):
            arm = "on" if async_io else "off"
            wall = min(walls[async_io])
            stall = min(stalls[async_io])
            row[arm] = {"wall_s": round(wall, 3),
                        "io_stall_s": round(stall, 4),
                        "stall_share": round(stall / wall, 4)
                        if wall > 0 else None}
        row["overlap_s"] = round(overlap, 4)
        row["match"] = True
        return row

    out = {"rms": rms, "reps": reps,
           "ckpt_heavy": ab_pair("ckpt")}
    seg_dir = os.path.join(work, "segments")
    out["spill_capped"] = ab_pair(
        "tier", tier_device_bytes=40_000, tier_host_bytes=4096,
        tier_dir=seg_dir)
    RESULT["async_io"] = out


def _stage_headline(platform):
    """The north-star workload, bounded to a rate sample."""
    host_cap = int(os.environ.get("BENCH_HOST_CAP", "60000"))
    model, name, batch, table, tpu_cap, max_batch = build_workload(platform)

    host, host_rate, host_sec = _host_bfs(model, cap=host_cap)
    RESULT.update({
        "host_states_per_sec": round(host_rate, 1),
        "host_sec": round(host_sec, 2),
        "headline_pending": f"{name} device run did not finish",
    })
    # Leave the watchdog a margin to emit; a partial run still reports.
    deadline = _T0 + _BUDGET - min(30.0, _BUDGET * 0.12)
    tpu, tpu_rate, finished = _tpu_bfs(model, batch, table,
                                       cap=tpu_cap, deadline=deadline,
                                       max_batch=max_batch)
    tpu_states = tpu.state_count()
    tpu_unique = tpu.unique_state_count()
    RESULT["wave_scheduler"] = tpu.scheduler_stats()
    _hoist_succ_telemetry(RESULT["wave_scheduler"])
    if tpu_rate <= 0:
        return  # no full wave completed; keep the parity-stage numbers
    del RESULT["headline_pending"]
    ran = ("cap %d" % tpu_cap if finished
           else "partial: deadline before cap")

    def _set_headline(baseline_rate, baseline_name):
        def compose():
            return (f"tpu_bfs states/sec on {platform}, {name} "
                    f"({tpu_states} states, {ran}; "
                    f"{_parity_clause()}; baseline = "
                    f"{baseline_name}, {os.cpu_count()} core(s))")

        _HEADLINE["recompose"] = compose
        RESULT.update({
            "metric": compose(),
            "value": round(tpu_rate, 1),
            "unit": "states/sec",
            "vs_baseline": round(tpu_rate / max(baseline_rate, 1e-9), 3),
            "vs_python_host": round(tpu_rate / max(host_rate, 1e-9), 3),
            "tpu_states": tpu_states,
            "tpu_unique": tpu_unique,
        })

    # Publish with the Python baseline first, then upgrade to the honest
    # compiled baseline — run AFTER the device stage so its first-use g++
    # compile + full-space enumeration can never eat the device window,
    # and only with budget left for it (the watchdog emits whatever the
    # last completed update produced).
    _set_headline(host_rate, "Python spawn_bfs")
    if _remaining() > 40:
        try:
            native_rate = _native_bfs_rate(model)
        except Exception as e:  # noqa: BLE001 — keep the Python baseline
            RESULT["native_baseline_error"] = \
                f"{type(e).__name__}: {e}"[:300]
            native_rate = None
        if native_rate:
            RESULT["native_host_states_per_sec"] = round(native_rate, 1)
            _set_headline(native_rate, "native C++ spawn_bfs")


def _stage_service(platform) -> None:
    """Checking-as-a-service satellite (BENCH_SERVICE_JOBS=N): submits
    N concurrent small jobs to an in-process ``JobService`` and reports
    aggregate throughput plus the shared wave-program cache's hit
    ratio under ``RESULT["service"]`` — the many-small-checks axis
    (ROADMAP item 5), where the win is amortization: job 1 pays the
    XLA compiles, jobs 2..N reuse the executables. ``cold_sec`` vs
    ``warm_sec_median`` is the measured gap (same-model jobs,
    wall-clock per job)."""
    import tempfile

    from stateright_tpu.service import JobService

    n_jobs = int(os.environ.get("BENCH_SERVICE_JOBS", "0"))
    if n_jobs <= 0:
        return
    workers = int(os.environ.get("BENCH_SERVICE_WORKERS", "2"))
    model = os.environ.get("BENCH_SERVICE_MODEL", "twopc")
    svc = JobService(workers=workers,
                     data_dir=tempfile.mkdtemp(prefix="stpu-bench-svc-"))
    deadline = time.monotonic() + max(10.0, _remaining() - 10.0)
    t0 = time.monotonic()
    ids = [svc.submit({"model": model,
                       "knobs": {"batch_size": 64}})["id"]
           for _ in range(n_jobs)]
    stats = {"jobs": n_jobs, "model": model, "workers": workers}
    try:
        done = []
        while len(done) < n_jobs and time.monotonic() < deadline:
            statuses = [svc.status(j) for j in ids]
            done = [s for s in statuses
                    if s["state"] not in ("queued", "running")]
            time.sleep(0.1)
        wall = time.monotonic() - t0
        finished = [s for s in done if s["state"] == "done"]
        runtimes = sorted(s["runtime_s"] for s in finished
                          if s.get("runtime_s") is not None)
        cache = svc.program_cache.stats()
        stats.update({
            "finished": len(finished),
            "wall_sec": round(wall, 3),
            "jobs_per_sec": round(len(finished) / max(wall, 1e-9), 3),
            "cache_hits": cache["hits"],
            "cache_misses": cache["misses"],
            "cache_hit_ratio": cache["hit_ratio"],
            # Cold vs warm job latency: the slowest job carried the
            # compiles (jobs race, so max ~ cold), the median of the
            # rest ran warm.
            "cold_sec": runtimes[-1] if runtimes else None,
            "warm_sec_median": (runtimes[len(runtimes) // 2]
                                if len(runtimes) > 1 else None),
        })
        if len(finished) < n_jobs:
            stats["error"] = (f"{n_jobs - len(finished)} job(s) not "
                              "finished at the stage deadline")
    finally:
        svc.close()
        RESULT["service"] = stats


def _stage_soak(platform) -> None:
    """Sustained-traffic soak (BENCH_SOAK_JOBS=N): replays ONE arrival
    schedule of N same-shape jobs against two service configurations
    on the same box — cross-job wave multiplexing on (round 16: jobs
    share device waves as tenants of one engine) vs off (one engine
    per job, the round-14 baseline) — and reports aggregate jobs/s and
    p50/p99 per-job latency (submit to observed completion, queue wait
    included) under ``RESULT["soak"]``. With BENCH_SOAK_MIX=preempt
    (the default) one mid-schedule job is preempted and resumed in
    EACH arm, so the latency tail is measured with a drain + resume in
    flight, not on an undisturbed queue. The arms' per-job counters
    must agree pairwise (the differential suite pins solo identity;
    the A/B pins arm identity on live traffic) — a mismatch sets
    ``parity_failed``."""
    import tempfile

    from stateright_tpu.service import JobService

    n_jobs = int(os.environ.get("BENCH_SOAK_JOBS", "0"))
    if n_jobs <= 0:
        return
    arrival = float(os.environ.get("BENCH_SOAK_ARRIVAL", "0.05"))
    mix = os.environ.get("BENCH_SOAK_MIX", "preempt")
    inject = mix == "preempt"
    # BENCH_SOAK_MIX=crash (round 17): arm a torn-checkpoint fault in
    # EACH arm instead of a preempt — the mux arm's group crash now
    # routes through the Supervisor like the solo arm's, and the
    # pairwise counters_identical gate below IS the drill: per-tenant
    # counters must survive a mid-run crash of the shared engine.
    crash = mix == "crash"
    model = os.environ.get("BENCH_SERVICE_MODEL", "twopc")
    workers = int(os.environ.get("BENCH_SERVICE_WORKERS",
                                 str(min(8, n_jobs))))
    spec = {"model": model, "knobs": {"batch_size": 64}}
    if crash:
        # A small cadence so every job reaches checkpoint rest points.
        spec["knobs"]["checkpoint_every_waves"] = 2

    def _arm(mux: bool, deadline: float) -> dict:
        svc = JobService(
            workers=workers, mux=mux,
            data_dir=tempfile.mkdtemp(prefix="stpu-bench-soak-"))
        if crash:
            from stateright_tpu.resilience import (FAULTS_ENV,
                                                   reset_fault_plans)

            os.environ[FAULTS_ENV] = "torn_ckpt@n=2"
            reset_fault_plans()
        try:
            t0 = time.monotonic()
            submit_t, done_t, finals = {}, {}, {}
            ids = []
            victim = None
            for i in range(n_jobs):
                jid = svc.submit(dict(spec))["id"]
                ids.append(jid)
                submit_t[jid] = time.monotonic()
                if inject and i == n_jobs // 2:
                    # Preempt the FIRST job mid-schedule: by now it is
                    # running (or already done on a very fast box —
                    # then there is nothing to drain and the arm runs
                    # undisturbed; "preempts" reports what landed).
                    victim = ids[0]
                    try:
                        svc.preempt(victim)
                    except Exception:  # noqa: BLE001 — already done
                        victim = None
                if arrival > 0:
                    time.sleep(arrival)
            resumed_from = {}
            preempts = 0
            while time.monotonic() < deadline:
                open_ids = [j for j in ids if j not in done_t]
                if not open_ids:
                    break
                for jid in open_ids:
                    s = svc.status(jid)
                    if s["state"] in ("queued", "running"):
                        continue
                    if s["state"] == "preempted" \
                            and jid not in resumed_from.values():
                        # Resume continues the SAME logical job: its
                        # latency clock keeps running from the original
                        # submission.
                        rid = svc.submit({"resume": jid})["id"]
                        ids[ids.index(jid)] = rid
                        submit_t[rid] = submit_t.pop(jid)
                        resumed_from[rid] = jid
                        preempts += 1
                        continue
                    done_t[jid] = time.monotonic()
                    finals[jid] = (s["state"], s.get("states"),
                                   s.get("unique"))
                time.sleep(0.05)
            wall = time.monotonic() - t0
            lats = sorted(done_t[j] - submit_t[j] for j in done_t)
            finished = [j for j in done_t if finals[j][0] == "done"]
            stats = {
                "finished": len(finished),
                "preempts": preempts,
                "wall_sec": round(wall, 3),
                "jobs_per_sec": round(len(finished) / max(wall, 1e-9),
                                      3),
                "p50_sec": (round(lats[len(lats) // 2], 3)
                            if lats else None),
                "p99_sec": (round(lats[min(len(lats) - 1,
                                           int(len(lats) * 0.99))], 3)
                            if lats else None),
                "counters": sorted(finals[j][1:] for j in finished),
            }
            if len(finished) < n_jobs:
                stats["error"] = (f"{n_jobs - len(finished)} job(s) "
                                  "not finished at the arm deadline")
            return stats
        finally:
            svc.close()
            if crash:
                from stateright_tpu.resilience import (FAULTS_ENV,
                                                       reset_fault_plans)

                os.environ.pop(FAULTS_ENV, None)
                reset_fault_plans()

    stats = {"jobs": n_jobs, "model": model, "workers": workers,
             "arrival_sec": arrival, "mix": mix}
    # Half the remaining budget per arm, multiplexed first.
    for key, mux in (("mux", True), ("solo", False)):
        budget = max(15.0, (_remaining() - 10.0) / 2.0)
        stats[key] = _arm(mux, time.monotonic() + budget)
    mux_c = stats["mux"].pop("counters", [])
    solo_c = stats["solo"].pop("counters", [])
    stats["counters_identical"] = bool(mux_c) and mux_c == solo_c
    stats["speedup"] = round(
        stats["mux"]["jobs_per_sec"]
        / max(stats["solo"]["jobs_per_sec"], 1e-9), 3)
    if not stats["counters_identical"]:
        RESULT["parity_failed"] = True
        stats["error"] = (stats.get("error", "") +
                          " per-job counters differ between the "
                          "mux and solo arms").strip()
    RESULT["soak"] = stats


def _stage_soak_trace(platform) -> None:
    """Replayable open-loop overload A/B (BENCH_SOAK_TRACE=gen|PATH,
    round 21): loads a tools/traffic_gen arrival trace — or, with
    ``gen``, generates one under a bench tempdir from
    BENCH_SOAK_TRACE_SEED — and replays the SAME schedule (arrival
    times, priorities, tenants, deadlines all pre-sampled at
    generation time) against two live services on this box: overload
    controller ON (explicit :class:`OverloadController`) vs OFF (the
    shared disarmed ``NULL_CONTROL``). The replay is OPEN LOOP:
    submissions are held to the trace clock regardless of service
    state, so the ON arm's 429s are admission decisions and the OFF
    arm's failures are raw queue overflow — the contrast the
    controller exists to create. Goodput, interactive deadline
    hit-rate and p99, sheds by reason, and park/resume counts land
    under ``RESULT["soak_trace"]``. Single-host honesty: both arms
    share one box with the bench process itself (and on a 1-core
    runner with each other's leftover page cache), so compare the
    arms to each other, never to absolute SLO targets."""
    import tempfile

    trace_spec = os.environ.get("BENCH_SOAK_TRACE", "")
    if not trace_spec:
        return
    sys.path.insert(0, os.path.join(_ROOT, "tools"))
    import traffic_gen

    from stateright_tpu.service import (NULL_CONTROL, ControlPolicy,
                                        JobQueueFull, JobService,
                                        JobShed, OverloadController)

    if trace_spec == "gen":
        trace = traffic_gen.gen_trace(
            seed=int(os.environ.get("BENCH_SOAK_TRACE_SEED", "0")),
            duration_s=float(
                os.environ.get("BENCH_SOAK_TRACE_DURATION", "6")),
            rate_hz=float(os.environ.get("BENCH_SOAK_TRACE_RATE",
                                         "4")))
        trace_path = os.path.join(
            tempfile.mkdtemp(prefix="stpu-bench-trace-"),
            "traffic.jsonl")
        traffic_gen.write_trace(trace, trace_path)
    else:
        trace = traffic_gen.load_trace(trace_spec)
        trace_path = trace_spec
    arrivals = trace["arrivals"]
    model = os.environ.get("BENCH_SERVICE_MODEL", "twopc")
    workers = int(os.environ.get("BENCH_SERVICE_WORKERS", "2"))
    max_queued = int(os.environ.get("BENCH_SOAK_TRACE_QUEUE", "16"))

    # Both arms observe under the SAME armed SLO surface (the burn
    # signal the ON arm's controller consumes; the OFF arm measures
    # but never acts) — thresholds tight enough that a real overload
    # burns budget within the replay window.
    slo_spec = os.environ.get("BENCH_SOAK_TRACE_SLO",
                              "job_latency=1.0,queue_wait=0.3,"
                              "window=10")

    def _arm(armed: bool, deadline: float) -> dict:
        control = (OverloadController(ControlPolicy()) if armed
                   else NULL_CONTROL)
        prev_slo = os.environ.get("STpu_SLO")
        os.environ["STpu_SLO"] = slo_spec
        try:
            svc = JobService(
                workers=workers, mux=True, max_queued=max_queued,
                data_dir=tempfile.mkdtemp(prefix="stpu-bench-ab-"),
                control=control)
        finally:
            if prev_slo is None:
                os.environ.pop("STpu_SLO", None)
            else:
                os.environ["STpu_SLO"] = prev_slo
        try:
            t0 = time.monotonic()
            open_jobs = {}  # live job id -> (arrival idx, submit wall)
            shed = []  # (arrival idx, reason)
            final = {}  # arrival idx -> (latency_s, terminal state)
            for i, arr in enumerate(arrivals):
                wait = arr["t"] - (time.monotonic() - t0)
                if wait > 0:
                    time.sleep(wait)
                spec = {"model": model, "knobs": {"batch_size": 64},
                        "priority": arr["priority"],
                        "tenant": arr["tenant"]}
                if arr.get("deadline_s"):
                    spec["deadline_s"] = arr["deadline_s"]
                try:
                    jid = svc.submit(spec)["id"]
                except JobShed as e:
                    shed.append((i, e.reason))
                    continue
                except JobQueueFull:
                    # The disarmed arm's only refusal mode: raw
                    # overflow, blind to priority.
                    shed.append((i, "queue_full"))
                    continue
                open_jobs[jid] = (i, time.monotonic())
            while open_jobs and time.monotonic() < deadline:
                listing = {p["id"]: p for p in svc.jobs()}
                for p in listing.values():
                    # A controller park resumes as a NEW job id; the
                    # successor inherits the original's latency clock
                    # (parking must not launder queue wait).
                    prev = p.get("resume_of")
                    if prev in open_jobs and p["id"] not in open_jobs:
                        open_jobs[p["id"]] = open_jobs.pop(prev)
                for jid in list(open_jobs):
                    st = listing.get(jid)
                    if st is None or st["state"] in (
                            "queued", "running", "preempted"):
                        continue  # preempted = parked, resume pending
                    idx, sub_t = open_jobs.pop(jid)
                    final[idx] = (time.monotonic() - sub_t,
                                  st["state"])
                time.sleep(0.05)
            wall = time.monotonic() - t0
            by_reason: dict = {}
            for _, reason in shed:
                by_reason[reason] = by_reason.get(reason, 0) + 1
            inter = [i for i, a in enumerate(arrivals)
                     if a["kind"] == "interactive"]
            inter_done = [(i, final[i][0]) for i in inter
                          if final.get(i, (0, ""))[1] == "done"]
            inter_lats = sorted(lat for _, lat in inter_done)
            done = [i for i in final if final[i][1] == "done"]
            stats = {
                "finished": len(done),
                "shed": len(shed),
                "shed_by_reason": by_reason,
                "interactive_total": len(inter),
                "interactive_shed": sum(
                    1 for i, _ in shed
                    if arrivals[i]["kind"] == "interactive"),
                "interactive_deadline_met": sum(
                    1 for i, lat in inter_done
                    if lat <= (arrivals[i].get("deadline_s")
                               or float("inf"))),
                "interactive_p99_s": (round(
                    inter_lats[min(len(inter_lats) - 1,
                                   int(len(inter_lats) * 0.99))], 3)
                    if inter_lats else None),
                "goodput_jobs_s": round(
                    len(done) / max(wall, 1e-9), 3),
                "wall_s": round(wall, 3),
                "unfinished": len(open_jobs),
            }
            ctl = svc.control_status()
            if ctl is not None:
                stats["park_total"] = ctl["park_total"]
                stats["resume_total"] = ctl["resume_total"]
                stats["shed_total"] = ctl["shed_total"]
                stats["final_rung"] = ctl["rung"]
            return stats
        finally:
            svc.close()

    stats = {"trace": trace_path, "arrivals": len(arrivals),
             "model": model, "workers": workers,
             "queue_bound": max_queued}
    for key, armed in (("control_on", True), ("control_off", False)):
        budget = max(20.0, (_remaining() - 10.0) / 2.0)
        stats[key] = _arm(armed, time.monotonic() + budget)
    on, off = stats["control_on"], stats["control_off"]
    if on["interactive_total"]:
        stats["interactive_met_delta"] = (
            on["interactive_deadline_met"]
            - off["interactive_deadline_met"])
    RESULT["soak_trace"] = stats


def _resolve_device() -> None:
    """Names the device the whole bench runs on, in this process. A TPU,
    or an explicit ``BENCH_PLATFORM=cpu`` rehearsal; anything else ends
    the bench with a non-zero exit and no rate."""
    import jax

    rehearsal = os.environ.get("BENCH_PLATFORM")
    if rehearsal not in (None, "", "cpu"):
        raise SystemExit(f"BENCH_PLATFORM={rehearsal!r}: only 'cpu' "
                         "(a rehearsal) may be forced")
    if rehearsal == "cpu":
        jax.config.update("jax_platforms", "cpu")
    devices = jax.devices()
    RESULT["platform"] = devices[0].platform
    RESULT["device"] = {"platform": devices[0].platform,
                        "kind": devices[0].device_kind,
                        "count": len(devices)}
    if RESULT["platform"] != "tpu" and rehearsal != "cpu":
        RESULT["error"] = (f"no TPU: JAX resolved {RESULT['platform']}; "
                           "set BENCH_PLATFORM=cpu for a CPU rehearsal")
        _emit_and_exit(2)


def main() -> None:
    threading.Thread(target=_watchdog, daemon=True).start()
    _resolve_device()

    # Run telemetry (obs subsystem): with STpu_TRACE set, every engine
    # this process spawns streams its wave events to one JSONL file,
    # and the bench's own stages land as spans in the same stream — the
    # whole round is one Perfetto-loadable capture. The scheduler/
    # ladder/local-dedup stats forwarded below are views over that
    # same event stream (engine dispatch_log == serialized wave
    # events), not parallel bookkeeping.
    from stateright_tpu.obs import tracer_from_env

    tracer = tracer_from_env("bench", meta={"budget_s": _BUDGET})
    if tracer.enabled:
        RESULT["trace"] = tracer.path

    # Tiered-store knobs (ISSUE 8): BENCH_TIER_* map onto the engines'
    # STpu_TIER_* env knobs BEFORE any stage spawns an engine, so the
    # engines run under the same tier budgets.
    for bench_key, env_key in (("BENCH_TIER_DEVICE_CAP",
                                "STpu_TIER_DEVICE_BYTES"),
                               ("BENCH_TIER_RAM_CAP",
                                "STpu_TIER_HOST_BYTES"),
                               ("BENCH_TIER_DIR", "STpu_TIER_DIR")):
        if os.environ.get(bench_key):
            os.environ[env_key] = os.environ[bench_key]
    # Continuous-profiler knob (ISSUE 18): BENCH_PROF=1 arms STpu_PROF
    # for every stage; _hoist_succ_telemetry lifts the headline
    # engine's per-program roofline gauges into RESULT["prof"]. An
    # explicit STpu_PROF=0 in the ambient env wins (setdefault).
    if os.environ.get("BENCH_PROF") == "1":
        os.environ.setdefault("STpu_PROF", "1")
        if os.environ.get("BENCH_PROF_SAMPLE"):
            os.environ["STpu_PROF_SAMPLE"] = \
                os.environ["BENCH_PROF_SAMPLE"]

    stages = (_stage_parity_gate, _stage_headline)
    if os.environ.get("BENCH_TIER_DRILL") == "1":
        stages = stages + (_stage_tier_drill,)
    if os.environ.get("BENCH_ASYNC_IO") == "1":
        stages = stages + (_stage_async_io,)
    if int(os.environ.get("BENCH_SERVICE_JOBS", "0") or 0) > 0:
        stages = stages + (_stage_service,)
    if int(os.environ.get("BENCH_SOAK_JOBS", "0") or 0) > 0:
        stages = stages + (_stage_soak,)
    if os.environ.get("BENCH_SOAK_TRACE"):
        stages = stages + (_stage_soak_trace,)
    for stage in stages:
        try:
            with tracer.span(stage.__name__,
                             platform=RESULT["platform"]):
                stage(RESULT["platform"])
        except Exception as e:  # noqa: BLE001 — always emit the JSON line
            prior = RESULT.get("error")
            RESULT["error"] = (f"{prior}; " if prior else "") + \
                f"{stage.__name__}: {type(e).__name__}: {e}"
            # The other stage still runs: a headline failure must not
            # zero the bench (the parity stage provides the fallback
            # rate sample); a parity failure is recorded machine-
            # readably and stamped on the metric below.
            if stage is _stage_parity_gate:
                _PARITY["status"] = "failed"
                RESULT["parity_failed"] = True
    if _HEADLINE.get("recompose"):
        # Re-render the headline metric with the FINAL parity status.
        RESULT["metric"] = _HEADLINE["recompose"]()
    tracer.close()
    _emit_and_exit(0)


if __name__ == "__main__":
    main()
