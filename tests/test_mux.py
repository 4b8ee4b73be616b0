"""Cross-job wave multiplexing (round 16): the differential gate.

The multiplexer's whole contract is that sharing a device wave is
INVISIBLE in every per-job surface — counters, verdicts, discovery
paths, checkpoint bytes. So the tests here are differentials against
solo runs of the same model, plus the queue-policy units (priority,
quota, bounded admission) and the v9 trace-lint attribution window.

The fast tier keeps every run tiny (2pc @ 3 RMs — 288 unique states)
and shares ONE solo reference run across tests; the 8-job soak drill
and the cross-model matrix siblings run behind ``-m slow``.
"""

import json
import os
import sys
import time

import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_REPO, "tools"))

import trace_lint  # noqa: E402

from stateright_tpu.checkpoint_format import load_checkpoint  # noqa: E402
from stateright_tpu.jit_cache import WaveProgramCache  # noqa: E402
from stateright_tpu.service import (JobQueueFull, JobService,  # noqa: E402
                                    default_registry)
from stateright_tpu.service.jobs import _JobQueue  # noqa: E402
from stateright_tpu.service.mux import MuxGroup  # noqa: E402

#: One corpus shape shared by every fast test: small enough that a
#: full BFS is ~a dozen 32-wide waves, big enough to need several.
KNOBS = {"batch_size": 32, "table_capacity": 1 << 14,
         "checkpoint_every_waves": 1}


@pytest.fixture(scope="module")
def solo_twopc(tmp_path_factory):
    """The solo reference run every differential compares against."""
    d = tmp_path_factory.mktemp("solo")
    ckpt = str(d / "solo.npz")
    model, _ = default_registry().build("twopc", {"rm_count": 3})
    checker = model.checker().spawn_tpu_bfs(
        fused=False, batch_size=32, table_capacity=1 << 14,
        checkpoint_path=ckpt)
    checker.join()
    return {"model": model,
            "states": checker.state_count(),
            "unique": checker.unique_state_count(),
            "discoveries": {k: str(v)
                            for k, v in checker.discoveries().items()},
            "ckpt": ckpt}


def _assert_checkpoint_bytes_equal(path_a, path_b):
    # Per-section byte comparison: npz zip metadata carries timestamps,
    # so whole-file equality would flake; the ARRAYS must match.
    with load_checkpoint(path_a) as a, load_checkpoint(path_b) as b:
        assert sorted(a.files) == sorted(b.files)
        for name in sorted(a.files):
            assert (np.asarray(a[name]).tobytes()
                    == np.asarray(b[name]).tobytes()), name


# -- The differential gate -------------------------------------------------


def test_mux_differential_vs_solo(solo_twopc, tmp_path):
    """Three tenants of one shared-wave group each report exactly the
    solo run's counters, verdicts, and checkpoint bytes — and the
    group trace's per-job attribution sums to its wave totals."""
    cache = WaveProgramCache()
    group_trace = str(tmp_path / "mux.trace.jsonl")
    g = MuxGroup(solo_twopc["model"], knobs=dict(KNOBS),
                 program_cache=cache, program_key=("twopc", 3),
                 trace_path=group_trace)
    ckpts = [str(tmp_path / f"t{i}.npz") for i in range(3)]
    tenant_trace = str(tmp_path / "t0.trace.jsonl")
    handles = [g.admit(f"j-{i}", checkpoint_path=ckpts[i],
                       trace_path=tenant_trace if i == 0 else None)
               for i in range(3)]
    for h in handles:
        h.join()
    g.join(timeout=30)

    for h, ckpt in zip(handles, ckpts):
        assert not h.preempted
        assert h.state_count() == solo_twopc["states"]
        assert h.unique_state_count() == solo_twopc["unique"]
        assert ({k: str(v) for k, v in h.discoveries().items()}
                == solo_twopc["discoveries"])
        _assert_checkpoint_bytes_equal(solo_twopc["ckpt"], ckpt)

    # The group shared ONE compiled program across the three tenants.
    stats = [h.scheduler_stats() for h in handles]
    assert all(s["engine"] == "mux" for s in stats)
    assert sum(s["program_cache"]["hits"] for s in stats) >= 2
    assert max(s["jobs_in_group_high_water"] for s in stats) == 3

    # Group trace: every total's deltas equal the sum of its attributed
    # lines (the lint enforces per-window; here the stream aggregate).
    waves = [json.loads(l) for l in open(group_trace)
             if json.loads(l).get("type") == "wave"]
    totals = [w for w in waves if w["job_id"] is None]
    attr = [w for w in waves if w["job_id"] is not None]
    assert totals and attr
    for field in ("successors", "candidates", "novel"):
        assert (sum(a[field] for a in attr)
                == sum(t[field] for t in totals))
    for path in (group_trace, tenant_trace):
        counts, errors = trace_lint.lint_file(path)
        assert not errors, errors[:3]
        assert counts.get("wave", 0) > 0


def test_mux_preempt_resume_differential(solo_twopc, tmp_path):
    """Preempting ONE tenant at a wave boundary neither disturbs its
    co-scheduled job nor loses work: the resumed run finishes with
    solo-identical counters and checkpoint bytes."""
    cache = WaveProgramCache()
    g = MuxGroup(solo_twopc["model"], knobs=dict(KNOBS),
                 program_cache=cache, program_key=("twopc", 3))
    c0 = str(tmp_path / "t0.npz")
    h0 = g.admit("j-0", checkpoint_path=c0)
    h1 = g.admit("j-1", checkpoint_path=str(tmp_path / "t1.npz"))
    h0.preempt()  # lands at the next wave boundary
    h0.join()
    h1.join()
    g.join(timeout=30)

    # The co-tenant never noticed.
    assert not h1.preempted
    assert h1.state_count() == solo_twopc["states"]
    assert h1.unique_state_count() == solo_twopc["unique"]

    if not h0.preempted:
        # A fast box can drain j-0 before the flag lands — then the
        # run is simply done and must already match solo.
        assert h0.state_count() == solo_twopc["states"]
        return
    # Resume from the drained tenant's checkpoint generation, in a
    # FRESH group (the service does exactly this on resubmission).
    g2 = MuxGroup(solo_twopc["model"], knobs=dict(KNOBS),
                  program_cache=cache, program_key=("twopc", 3))
    h0r = g2.admit("j-0r", checkpoint_path=c0, resume_from=c0)
    h0r.join()
    g2.join(timeout=30)
    assert h0r.state_count() == solo_twopc["states"]
    assert h0r.unique_state_count() == solo_twopc["unique"]
    assert ({k: str(v) for k, v in h0r.discoveries().items()}
            == solo_twopc["discoveries"])
    _assert_checkpoint_bytes_equal(solo_twopc["ckpt"], c0)
    # The resumed admission re-used the already-built shared program.
    assert h0r.scheduler_stats()["program_cache"]["hits"] >= 1


# -- Queue policy ----------------------------------------------------------


def test_queue_priority_quota_and_bounds():
    # Priority: higher first, FIFO within a priority band.
    q = _JobQueue()
    for job, prio in (("a", 0), ("b", 5), ("c", 5), ("d", 1)):
        q.put(job, priority=prio)
    order = [q.pop()[0] for _ in range(4)]
    assert order == ["b", "c", "d", "a"]

    # Quota: a tenant at its running cap is SKIPPED, not starved.
    q = _JobQueue(tenant_quota=1)
    q.put("x", tenant="t")
    q.put("y", tenant="t")
    q.put("z", tenant="u")
    assert q.pop() == ("x", "t")
    assert q.pop() == ("z", "u")  # y skipped: t is at quota
    q.task_done("t")
    assert q.pop() == ("y", "t")

    # Bounded admission: overflow raises, cancel frees the slot.
    q = _JobQueue(max_queued=2)
    q.put("p")
    q.put("q")
    with pytest.raises(JobQueueFull):
        q.put("r")
    assert q.cancel("p")
    assert not q.cancel("p")  # already gone
    q.put("r")
    assert q.qsize() == 2


def test_service_admission_control_and_cancel(tmp_path):
    """Bounded-queue 429 semantics and DELETE-on-queued at the service
    layer, deterministically: the sole tenant is pinned at quota so
    its submissions can never be popped."""
    svc = JobService(workers=1, data_dir=str(tmp_path / "svc"),
                     max_queued=1, tenant_quota=1)
    try:
        # Pin tenant "t" at its running quota: queued jobs stay put.
        with svc._queue._cv:
            svc._queue._active["t"] = 1
        spec = {"model": "twopc", "knobs": {"batch_size": 32},
                "tenant": "t", "priority": 3}
        j1 = svc.submit(spec)
        assert svc.status(j1["id"])["state"] == "queued"
        assert svc.status(j1["id"])["priority"] == 3
        assert svc.status(j1["id"])["tenant"] == "t"

        # Queue full: the overflow is rejected AND leaves no record.
        with pytest.raises(JobQueueFull):
            svc.submit(spec)
        assert [p["id"] for p in svc.jobs()] == [j1["id"]]

        # DELETE on a queued job cancels it outright (nothing ran, so
        # nothing to resume) and frees the queue slot.
        out = svc.preempt(j1["id"])
        assert out["state"] == "cancelled"
        j2 = svc.submit(spec)
        assert svc.status(j2["id"])["state"] == "queued"

        # The cancelled job's trace pairs its submit with the abort.
        events = [json.loads(l)
                  for l in open(svc.trace_file(j1["id"]))]
        aborts = [e for e in events if e.get("type") == "job_abort"]
        assert aborts and aborts[0]["reason"] == "cancelled"
        _, errors = trace_lint.lint_file(svc.trace_file(j1["id"]))
        assert not errors, errors[:3]
    finally:
        svc.close()


def test_http_429_on_full_queue(tmp_path):
    from stateright_tpu.explorer import serve_service

    import service_client as sc

    # max_queued=0: every submission overflows — the HTTP mapping is
    # what's under test, not the queue.
    service, server = serve_service(
        addresses=("127.0.0.1", 0), block=False, workers=1,
        data_dir=str(tmp_path), max_queued=0)
    host, port = server.server_address[:2]
    try:
        # Round 21: a 429 is an admission DECISION the client handles,
        # not an exception — submit returns the shed payload.
        payload = sc.submit(f"http://{host}:{port}",
                            {"model": "twopc",
                             "knobs": {"batch_size": 32}})
        assert payload.get("shed") is True
        assert "full" in payload["error"]
    finally:
        server.shutdown()
        server.server_close()
        service.close()


# -- The v9 lint window ----------------------------------------------------


def _wave_line(run, wave, *, job_id=None, jobs_in_wave=None, succ=10,
               cand=8, novel=4, states=100, unique=50):
    return json.dumps({
        "type": "wave", "schema_version": 9, "engine": "mux",
        "run": run, "wave": wave, "t": 1.0 + wave, "states": states,
        "unique": unique, "bucket": 32, "waves": 1, "inflight": 0,
        "compiled": False, "successors": succ, "candidates": cand,
        "novel": novel, "out_rows": 64, "capacity": 1024,
        "load_factor": 0.1, "overflow": False, "bytes_per_state": 28,
        "arena_bytes": None, "table_bytes": 8192, "worker": None,
        "seq": None, "epoch": None, "round": None,
        "tier_device_rows": None, "tier_device_bytes": None,
        "tier_host_rows": None, "tier_host_bytes": None,
        "tier_disk_rows": None, "tier_disk_bytes": None,
        "kernel_path": "xla", "rows": 8, "job_id": job_id,
        "jobs_in_wave": jobs_in_wave})


def test_trace_lint_mux_attribution_window():
    """The v9 stream invariant, schema-level: a mux TOTAL wave must be
    followed by exactly ``jobs_in_wave`` attributed lines whose deltas
    sum to the total's, before anything else happens to the run."""
    # A correct window: total, then its two attributed lines.
    good = [_wave_line("r0", 0, jobs_in_wave=2, succ=10, cand=8,
                       novel=4),
            _wave_line("r0", 1, job_id="j-1", jobs_in_wave=2, succ=6,
                       cand=5, novel=3),
            _wave_line("r0", 2, job_id="j-2", jobs_in_wave=2, succ=4,
                       cand=3, novel=1)]
    _, errors = trace_lint.lint_lines(good)
    assert not errors, errors

    # A per-JOB trace file: attributed lines with no window are fine.
    _, errors = trace_lint.lint_lines(
        [_wave_line("t0", 0, job_id="j-1", jobs_in_wave=2),
         _wave_line("t0", 1, job_id="j-1", jobs_in_wave=2,
                    states=110, unique=55)])
    assert not errors, errors

    # Short attribution at end-of-stream.
    _, errors = trace_lint.lint_lines(good[:2])
    assert len(errors) == 1 and "never followed" in errors[0]

    # Short attribution cut off by run_end.
    run_end = json.dumps({"type": "run_end", "schema_version": 9,
                          "engine": "mux", "run": "r0", "t": 9.0,
                          "dur": 1.0, "counters": {}})
    _, errors = trace_lint.lint_lines(good[:2] + [run_end])
    assert len(errors) == 1 and "still awaiting" in errors[0]

    # A new total while the previous window is open.
    _, errors = trace_lint.lint_lines(
        [good[0],
         _wave_line("r0", 1, jobs_in_wave=2, succ=10, cand=8, novel=4),
         good[1].replace('"wave": 1', '"wave": 2'),
         good[2].replace('"wave": 2', '"wave": 3')])
    assert any("still awaits" in e for e in errors)

    # jobs_in_wave disagreement between a total and its attribution.
    _, errors = trace_lint.lint_lines(
        [good[0],
         _wave_line("r0", 1, job_id="j-1", jobs_in_wave=3, succ=6,
                    cand=5, novel=3),
         good[2]])
    assert len(errors) == 1 and "jobs_in_wave=3" in errors[0]

    # Deltas that don't sum to the total: fabricated accounting.
    _, errors = trace_lint.lint_lines(
        [good[0],
         _wave_line("r0", 1, job_id="j-1", jobs_in_wave=2, succ=3,
                    cand=5, novel=3),
         good[2]])
    assert len(errors) == 1 and "successors" in errors[0]

    # A solo wave inside an open window.
    _, errors = trace_lint.lint_lines(
        [good[0], _wave_line("r0", 1)])
    assert any("solo wave inside" in e for e in errors)


# -- Slow arms: the soak drill and the matrix siblings ---------------------


@pytest.mark.slow
def test_mux_soak_drill(tmp_path):
    """Eight concurrent same-shape jobs through the SERVICE, mux on vs
    off: identical per-job counters either way (the bench soak arm
    measures the throughput side of this same drill)."""
    spec = {"model": "twopc", "knobs": {"batch_size": 32}}
    results = {}
    for mux in (True, False):
        svc = JobService(workers=8, data_dir=str(tmp_path / f"m{mux}"),
                         mux=mux)
        try:
            ids = [svc.submit(spec)["id"] for _ in range(8)]
            deadline = time.monotonic() + 420
            while time.monotonic() < deadline:
                states = [svc.status(i)["state"] for i in ids]
                if all(s in ("done", "failed", "preempted")
                       for s in states):
                    break
                time.sleep(0.1)
            payloads = [svc.status(i) for i in ids]
            assert all(p["state"] == "done" for p in payloads), \
                [(p["id"], p["state"], p["error"]) for p in payloads]
            results[mux] = [(p["states"], p["unique"])
                            for p in payloads]
            assert all(p["jit_cache"]["shared"] for p in payloads)
            assert sum(p["jit_cache"]["hits"] for p in payloads) > 0
        finally:
            svc.close()
    assert results[True] == results[False]
    assert all(c == (1146, 288) for c in results[True])


@pytest.mark.slow
def test_mux_matrix_siblings():
    """The differential holds beyond 2pc: two tenants per group across
    other corpus shapes report solo-identical counters."""
    for name, params in (("pingpong", None), ("vsr", {"n": 2}),
                         ("increment_lock", None)):
        model, _ = default_registry().build(name, params)
        solo = model.checker().spawn_tpu_bfs(
            fused=False, batch_size=32, table_capacity=1 << 14)
        solo.join()
        g = MuxGroup(model, knobs={"batch_size": 32,
                                   "table_capacity": 1 << 14},
                     program_cache=WaveProgramCache(),
                     program_key=(name,))
        handles = [g.admit(f"{name}-{i}") for i in range(2)]
        for h in handles:
            h.join()
        g.join(timeout=60)
        for h in handles:
            assert h.state_count() == solo.state_count(), name
            assert h.unique_state_count() == \
                solo.unique_state_count(), name
            assert sorted(h.discoveries()) == \
                sorted(solo.discoveries()), name


@pytest.mark.slow
def test_mux_early_stop_equals_solo_at_effective_width():
    """The identity-scope boundary, pinned exactly: a run that stops
    EARLY (every property discovered before exhaustion) halts at a
    wave boundary, and the boundary's position depends on rows per
    wave — already true solo (batch 16 vs 32 stop at different
    counts). Two co-tenants splitting a 32-row wave see 16 rows each,
    so they match a SOLO batch-16 run bit-for-bit; exhaustive runs
    (every other differential here) are width-invariant and match
    solo at any batch size."""
    model, _ = default_registry().build("increment", None)
    solo16 = model.checker().spawn_tpu_bfs(
        fused=False, batch_size=16, table_capacity=1 << 14)
    solo16.join()
    assert solo16.discoveries()  # it DOES early-stop ('fin' violated)
    g = MuxGroup(model, knobs={"batch_size": 32,
                               "table_capacity": 1 << 14},
                 program_cache=WaveProgramCache(),
                 program_key=("increment",))
    handles = [g.admit(f"i-{i}") for i in range(2)]
    for h in handles:
        h.join()
    g.join(timeout=60)
    for h in handles:
        assert h.state_count() == solo16.state_count()
        assert h.unique_state_count() == solo16.unique_state_count()


#: paxos check 4 under client symmetry: the orbits of its 2,372,188
#: states (tests/test_paxos_symmetry.py)
PAXOS4_SYM = {"model": "paxos",
              "params": {"client_count": 4, "symmetry": True}}


def test_a_symmetric_job_runs_solo(tmp_path):
    """A group has no symmetry path and would check the raw space, so a
    job whose model checks under symmetry never joins one: with the mux
    on it is not mux-eligible, a group refuses its model, and the solo
    engine the service builds for it dedups by the representative. The
    same job without ``symmetry`` is mux-eligible."""
    svc = JobService(workers=1, data_dir=str(tmp_path / "svc"),
                     tenant_quota=1)
    try:
        # Pin tenant "t" at its running quota: the jobs stay queued.
        with svc._queue._cv:
            svc._queue._active["t"] = 1
        jobs = {}
        for sym in (False, True):
            spec = dict(PAXOS4_SYM, knobs={"batch_size": 64}, tenant="t")
            spec["params"] = dict(spec["params"], symmetry=sym)
            jobs[sym] = svc._job(svc.submit(spec)["id"])
        assert svc._mux_eligible(jobs[False])
        assert not svc._mux_eligible(jobs[True])
        with pytest.raises(ValueError, match="symmetric"):
            MuxGroup(jobs[True].model, knobs={"batch_size": 64})
        checker = svc._factory(jobs[True])()
        checker.preempt()
        checker.join()
        assert checker._use_symmetry
        for job in jobs.values():
            svc.preempt(job.id)
    finally:
        svc.close()


@pytest.mark.slow
def test_a_symmetric_job_counts_orbits_with_the_mux_on(tmp_path):
    """paxos check 4 with ``symmetry`` through the service, mux on:
    1,194,428 orbits, not the raw space's 2,372,188 states."""
    svc = JobService(workers=1, data_dir=str(tmp_path / "svc"))
    try:
        job = svc.submit(dict(PAXOS4_SYM, knobs={
            "batch_size": 4096, "table_capacity": 1 << 22}))["id"]
        deadline = time.monotonic() + 1800
        while (svc.status(job)["state"] in ("queued", "running")
               and time.monotonic() < deadline):
            time.sleep(0.5)
        payload = svc.status(job)
        assert payload["state"] == "done", payload["error"]
        assert payload["unique"] == 1_194_428
    finally:
        svc.close()
