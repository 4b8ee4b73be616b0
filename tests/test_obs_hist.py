"""Service-level observability (round 18): deterministic latency
histograms, the SLO/health surface, and slow-wave anomaly attribution.

Contracts pinned here:

- **Deterministic and mergeable**: the fixed power-of-two bucket
  ladder means the same sample sequence always produces the same
  snapshot, and two histograms of one series merge by element-wise
  addition; the Prometheus exposition's cumulative ``le`` buckets are
  exact over it.
- **Disarmed means free**: with no ``STpu_HIST``/``STpu_SLO``/
  ``STpu_ANOMALY`` knob set the engines hold the shared ``NULL_OBS``
  singleton and the wave loop NEVER calls into it (the null methods
  are poisoned) — mirroring the round-8 tracer contract.
- **Armed end to end**: an armed engine run emits schema-v11
  ``hist_snapshot`` events that lint clean, export to
  ``_bucket``/``_sum``/``_count`` families, and surface p50/p99 in
  ``tools/trace_summary.py``; counts stay bit-identical to a host run.
- **SLO lifecycle**: breaches are edge-triggered (one event per
  transition), recovery is silent, ``/.healthz`` answers 200/503, and
  a disarmed server still answers 200.
- **Anomaly attribution**: the per-key EWMA+MAD detector names the
  cause — compile, io_stall, straggler, spill — from gauges the wave
  entry already carries.

The full service soak (jobs + live /.healthz + /.metrics mid-run)
runs behind ``-m slow``.
"""

import json
import os
import subprocess
import sys
import time

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_REPO, "examples"))
sys.path.insert(0, os.path.join(_REPO, "tools"))

from two_phase_commit import TwoPhaseSys  # noqa: E402

from stateright_tpu.obs import SCHEMA_VERSION, validate_event  # noqa: E402
from stateright_tpu.obs.anomaly import (SlowWaveDetector,  # noqa: E402
                                        detector_from_env)
from stateright_tpu.obs.hist import (BUCKET_BOUNDS, NULL_OBS,  # noqa: E402
                                     Histogram, HistogramSet,
                                     NullWaveObs, WaveObs,
                                     bucket_quantile, parse_series_key,
                                     prometheus_hist_lines, series_key,
                                     wave_obs_from_env)
from stateright_tpu.obs.slo import (MIN_SAMPLES, SloTracker,  # noqa: E402
                                    prometheus_slo_lines, slo_from_env)

import trace_export  # noqa: E402
import trace_lint  # noqa: E402
import trace_summary  # noqa: E402

_OBS_KNOBS = ("STpu_HIST", "STpu_SLO", "STpu_ANOMALY", "STpu_HIST_SNAP_S")


def _events(path):
    with open(path, encoding="utf-8") as f:
        return [json.loads(line) for line in f if line.strip()]


def _disarm(monkeypatch):
    for knob in _OBS_KNOBS:
        monkeypatch.delenv(knob, raising=False)


# -- Histogram core --------------------------------------------------------


def test_histogram_deterministic_and_mergeable():
    samples = [1e-6, 0.003, 0.003, 0.8, 50.0, 100.0]
    a, b = Histogram(), Histogram()
    for s in samples:
        a.observe(s)
        b.observe(s)
    assert a.snapshot() == b.snapshot()
    snap = a.snapshot()
    assert snap["count"] == len(samples)
    assert sum(snap["buckets"]) == snap["count"]  # NON-cumulative
    assert snap["sum"] == pytest.approx(sum(samples))
    # 100 s is beyond the 64 s top bound: the implicit +Inf bucket.
    assert snap["buckets"][len(BUCKET_BOUNDS)] == 1
    # Merge is element-wise addition — doubling every count.
    a.merge(b)
    merged = a.snapshot()
    assert merged["count"] == 2 * len(samples)
    assert merged["buckets"] == [2 * c for c in snap["buckets"]]


def test_bucket_quantile_estimates():
    h = Histogram()
    assert h.quantile(0.5) is None
    for _ in range(99):
        h.observe(0.001)
    h.observe(10.0)
    # p50 reports the bucket upper bound holding 0.001.
    p50 = h.quantile(0.5)
    assert p50 in BUCKET_BOUNDS and 0.001 <= p50 <= 0.002
    assert h.quantile(0.99) == p50
    assert h.quantile(1.0) >= 10.0
    # The +Inf bucket saturates to the last finite bound.
    top = Histogram()
    top.observe(1e9)
    assert top.quantile(0.5) == BUCKET_BOUNDS[-1]


def test_series_key_roundtrip():
    key = series_key("wave_latency_seconds",
                     {"kernel_path": "fused", "engine": "classic"})
    # Labels sort — one deterministic identity per series.
    assert key == ('wave_latency_seconds{engine="classic",'
                   'kernel_path="fused"}')
    assert parse_series_key(key) == (
        "wave_latency_seconds",
        {"engine": "classic", "kernel_path": "fused"})
    assert parse_series_key("plain") == ("plain", {})


def test_prometheus_hist_lines_cumulative():
    hs = HistogramSet()
    for v in (0.001, 0.004, 0.004, 30.0, 1000.0):
        hs.observe("wave_latency_seconds", v, engine="classic",
                   kernel_path="none")
    lines = prometheus_hist_lines(hs.snapshot())
    assert "# TYPE stpu_wave_latency_seconds histogram" in lines
    buckets = [ln for ln in lines if "_bucket{" in ln]
    # One line per finite bound plus +Inf, cumulative and monotone.
    assert len(buckets) == len(BUCKET_BOUNDS) + 1
    counts = [int(ln.rsplit(" ", 1)[1]) for ln in buckets]
    assert counts == sorted(counts)
    assert buckets[-1].startswith(
        'stpu_wave_latency_seconds_bucket{engine="classic",'
        'kernel_path="none",le="+Inf"}')
    assert counts[-1] == 5
    sums = [ln for ln in lines if ln.startswith(
        "stpu_wave_latency_seconds_sum")]
    assert float(sums[0].rsplit(" ", 1)[1]) == pytest.approx(1030.009)
    assert any(ln.endswith(" 5") and "_count{" in ln for ln in lines)


# -- Disarmed cost ---------------------------------------------------------


def test_obs_disarmed_zero_cost(monkeypatch):
    """No obs knob set: the engines hold the NULL_OBS singleton and
    the wave loop never calls into it — every null method is poisoned,
    so a single stray call (= a stray per-wave cost with the subsystem
    off) fails the run."""
    _disarm(monkeypatch)
    assert wave_obs_from_env("classic") is NULL_OBS

    def _boom(name):
        def poisoned(self, *a, **k):
            raise AssertionError(
                f"NullWaveObs.{name} called with obs disarmed")
        return poisoned

    for name in ("wave", "job", "elastic_report", "maybe_snapshot",
                 "close"):
        monkeypatch.setattr(NullWaveObs, name, _boom(name))

    model = TwoPhaseSys(3)
    c = model.checker().spawn_tpu_bfs(batch_size=64, fused=False).join()
    assert c._wave_obs is NULL_OBS
    host = model.checker().spawn_bfs().join()
    assert host._wave_obs is NULL_OBS
    assert c.unique_state_count() == host.unique_state_count()


# -- Armed end to end ------------------------------------------------------


def test_armed_engine_snapshots_lint_export_summary(tmp_path,
                                                    monkeypatch):
    """An armed classic run: hist_snapshot events ride the trace,
    lint clean under v11, export to cumulative Prometheus families,
    surface p50/p99 in trace_summary — and discovery counts stay
    bit-identical to a disarmed host run."""
    path = tmp_path / "armed.jsonl"
    monkeypatch.setenv("STpu_TRACE", str(path))
    monkeypatch.setenv("STpu_HIST", "1")
    monkeypatch.setenv("STpu_SLO", "1")
    monkeypatch.setenv("STpu_ANOMALY", "1")
    monkeypatch.setenv("STpu_HIST_SNAP_S", "0.05")
    model = TwoPhaseSys(3)
    c = model.checker().spawn_tpu_bfs(batch_size=64, fused=False).join()
    for knob in ("STpu_TRACE",) + _OBS_KNOBS:
        monkeypatch.delenv(knob)

    events = _events(path)
    snaps = [e for e in events if e["type"] == "hist_snapshot"]
    assert snaps, "no hist_snapshot rode the trace"
    for e in snaps:
        assert validate_event(e) == [], e
        assert e["schema_version"] == SCHEMA_VERSION
        for key, data in e["hists"].items():
            assert sum(data["buckets"]) == data["count"], key
    # Cumulative across snapshots: counts never shrink.
    last = snaps[-1]["hists"]
    key = next(k for k in last if k.startswith("wave_latency_seconds"))
    assert last[key]["count"] >= snaps[0]["hists"].get(
        key, {"count": 0})["count"]
    _, labels = parse_series_key(key)
    assert labels["engine"] == "classic"

    counts, errors = trace_lint.lint_file(str(path))
    assert not errors, errors[:3]
    assert counts["hist_snapshot"] == len(snaps)

    prom = trace_export.to_prometheus(events)
    assert "stpu_wave_latency_seconds_bucket" in prom
    assert 'le="+Inf"' in prom
    assert "stpu_wave_latency_seconds_count" in prom

    table = trace_summary.format_table(trace_summary.summarize(events))
    assert "p50_ms" in table and "p99_ms" in table
    # The classic row carries numeric quantiles, not "-". (The name
    # column is "classic <run>" — two tokens — so p50/p99 sit at 5/6.)
    row = next(ln for ln in table.splitlines() if "classic" in ln)
    assert row.split()[5] != "-" and row.split()[6] != "-"

    # The live facade agrees with the stream.
    assert c._wave_obs.enabled
    assert c._wave_obs.slo_status()["healthy"]
    host = model.checker().spawn_bfs().join()
    assert c.unique_state_count() == host.unique_state_count()
    assert c.state_count() == host.state_count()


def test_trace_summary_gap_fallback():
    """v10-and-older captures (no hist_snapshot): p50/p99 fall back to
    exact percentiles over the raw wave time gaps."""
    events = [{"type": "wave", "engine": "classic", "run": "r0",
               "t": 1.0 + 0.01 * i, "states": 10 * i}
              for i in range(12)]
    rows = trace_summary.summarize(events)
    r = rows["classic r0"]
    assert not r["hist"] and len(r["gaps"]) == 11
    table = trace_summary.format_table(rows)
    row = next(ln for ln in table.splitlines() if "classic" in ln)
    assert row.split()[5] == "10.0"  # 10 ms gaps, exact


# -- SLO lifecycle ---------------------------------------------------------


def test_slo_breach_edge_triggered_and_recovers():
    slo = SloTracker({"wave_success": (None, 0.9)}, window_s=60.0)
    t = 100.0
    for _ in range(MIN_SAMPLES):
        assert slo.observe("wave_success", ok=True, t=t) is None
        t += 0.1
    assert slo.healthy
    # Push the good ratio under target: exactly one breach payload.
    breaches = []
    for _ in range(5):
        evt = slo.observe("wave_success", ok=False, t=t)
        t += 0.1
        if evt is not None:
            breaches.append(evt)
    assert len(breaches) == 1
    evt = breaches[0]
    assert evt["objective"] == "wave_success"
    assert evt["burn"] > 1.0
    assert evt["good"] + evt["bad"] == MIN_SAMPLES + evt["bad"]
    st = slo.status()
    assert not st["healthy"]
    assert st["objectives"]["wave_success"]["breaching"]
    assert st["objectives"]["wave_success"]["breaches"] == 1
    # Recovery is silent: the bad events age out of the window.
    t += 120.0
    for _ in range(2 * MIN_SAMPLES):
        assert slo.observe("wave_success", ok=True, t=t) is None
        t += 0.1
    assert slo.healthy
    assert slo.status()["objectives"]["wave_success"]["breaches"] == 1
    # A second dip is a second edge.
    for _ in range(2 * MIN_SAMPLES):
        slo.observe("wave_success", ok=False, t=t)
        t += 0.1
    assert slo.status()["objectives"]["wave_success"]["breaches"] == 2


def test_slo_latency_objective_and_status_lines():
    slo = SloTracker({"job_latency": (0.5, 0.9)}, window_s=60.0)
    for _ in range(MIN_SAMPLES):
        slo.observe("job_latency", value=0.01)
    st = slo.status()
    assert st["healthy"]
    assert st["objectives"]["job_latency"]["ratio"] == 1.0
    lines = prometheus_slo_lines(st)
    assert "stpu_slo_healthy 1" in lines
    assert 'stpu_slo_burn{objective="job_latency"} 0.0' in lines
    assert ('stpu_slo_breaches_total{objective="job_latency"} 0'
            in lines)
    # Unknown objective name: ignored, not a crash.
    assert slo.observe("nope", ok=False) is None


def test_slo_from_env_overrides(monkeypatch):
    monkeypatch.delenv("STpu_SLO", raising=False)
    assert slo_from_env() is None
    monkeypatch.setenv("STpu_SLO", "0")
    assert slo_from_env() is None
    monkeypatch.setenv("STpu_SLO",
                       "job_latency=0.25,window=30,wave_success=0.5,"
                       "bogus=7,junk")
    slo = slo_from_env()
    assert slo.window_s == 30.0
    assert slo._objs["job_latency"]["threshold"] == 0.25
    assert slo._objs["wave_success"]["target"] == 0.5


# -- Anomaly attribution ---------------------------------------------------


def _warm(det, key, n=8, dur=0.01):
    for _ in range(n):
        assert det.observe(key, dur, {}) is None


def test_anomaly_attribution_causes():
    det = SlowWaveDetector(k=4.0, warmup=8, floor=0.001)
    _warm(det, "c|none")
    evt = det.observe("c|none", 1.0, {"compiled": True})
    assert evt["cause"] == "compile"
    assert evt["baseline_s"] == pytest.approx(0.01)

    _warm(det, "io|none")
    evt = det.observe("io|none", 1.0, {"io_stall_s": 0.9})
    assert evt["cause"] == "io_stall"

    _warm(det, "el|none")
    evt = det.observe("el|none", 1.0, {}, wait_s=0.8)
    assert evt["cause"] == "straggler"

    det.observe("sp|none", 0.01, {"tier_host_bytes": 100})
    _warm(det, "sp|none", n=7)
    evt = det.observe("sp|none", 1.0, {"tier_host_bytes": 500})
    assert evt["cause"] == "spill"

    _warm(det, "u|none")
    evt = det.observe("u|none", 1.0, {})
    assert evt["cause"] == "unknown"

    recent = det.recent()
    assert [e["cause"] for e in recent] == [
        "compile", "io_stall", "straggler", "spill", "unknown"]
    assert det.stats()["total"] == 5
    # A fast wave never trips; the baseline keeps adapting.
    assert det.observe("u|none", 0.01, {}) is None


def test_anomaly_detector_from_env(monkeypatch):
    monkeypatch.delenv("STpu_ANOMALY", raising=False)
    assert detector_from_env() is None
    monkeypatch.setenv("STpu_ANOMALY", "k=6,warmup=4,floor=0.01,bad=x")
    det = detector_from_env()
    assert (det.k, det.warmup, det.floor) == (6.0, 4, 0.01)


# -- Facade ----------------------------------------------------------------


class _StubTracer:
    enabled = True

    def __init__(self):
        self.events = []

    def event(self, etype, **fields):
        self.events.append((etype, fields))


def test_wave_obs_facade_jobs_and_snapshots():
    obs = WaveObs("service", hist=HistogramSet(),
                  slo=SloTracker({"queue_wait": (0.5, 0.9)}),
                  snap_s=9999.0)
    tr = _StubTracer()
    obs.job(queue_s=0.01, run_s=0.2, total_s=0.21, engine="classic",
            tracer=tr)
    snap = obs.hist.snapshot()
    for fam in ("job_queue_seconds", "job_run_seconds",
                "job_latency_seconds"):
        assert series_key(fam, {"engine": "classic"}) in snap
    obs.elastic_report("w0", compute_s=0.4, wait_s=0.1)
    assert series_key("elastic_compute_seconds",
                      {"worker": "w0"}) in obs.hist.snapshot()
    # close() flushes a final snapshot even before the cadence.
    obs.close(tr)
    assert tr.events and tr.events[-1][0] == "hist_snapshot"
    assert tr.events[-1][1]["snap"] == 1
    # The stamped variant (flight-recorder hook) validates standalone.
    evt = obs.final_snapshot_event()
    assert validate_event(evt) == []
    assert evt["snap"] == 2 and evt["run"] == "hist-service"


def test_flight_dump_carries_final_snapshot(tmp_path):
    from stateright_tpu.obs.flight import FlightRecorder

    obs = WaveObs("classic", hist=HistogramSet())
    obs.hist.observe("wave_latency_seconds", 0.01, engine="classic",
                     kernel_path="none")
    fr = FlightRecorder("classic", capacity=8,
                        directory=str(tmp_path))
    fr.set_hist_source(obs.final_snapshot_event)
    fr.record_event("fault", point="expand", hit=1, mode="crash")
    path = fr.dump("test")
    events = _events(path)
    assert events[0]["type"] == "postmortem"
    assert events[-1]["type"] == "hist_snapshot"
    assert "wave_latency_seconds" in str(events[-1]["hists"])
    counts, errors = trace_lint.lint_file(path)
    assert not errors, errors[:3]


# -- Lint invariants -------------------------------------------------------


def _snap_evt(run, snap, count, bucket0, total=None, t=1.0):
    return {"type": "hist_snapshot", "schema_version": SCHEMA_VERSION,
            "engine": "classic", "run": run, "t": t,
            "hists": {"wave_latency_seconds": {
                "buckets": [bucket0], "sum": total
                if total is not None else 0.01 * count,
                "count": count}},
            "snap": snap}


def test_lint_catches_hist_snapshot_violations(tmp_path):
    ok = tmp_path / "ok.jsonl"
    with open(ok, "w") as f:
        f.write(json.dumps(_snap_evt("r0", 1, 2, 2)) + "\n")
        f.write(json.dumps(_snap_evt("r0", 2, 5, 5, t=2.0)) + "\n")
    counts, errors = trace_lint.lint_file(str(ok))
    assert not errors and counts["hist_snapshot"] == 2

    def check(name, *evts):
        bad = tmp_path / name
        with open(bad, "w") as f:
            for e in evts:
                f.write(json.dumps(e) + "\n")
        _, errors = trace_lint.lint_file(str(bad))
        assert errors, name
        return errors

    # Buckets that don't sum to count.
    check("sum.jsonl", _snap_evt("r0", 1, 3, 2))
    # Count shrank between snapshots (cumulative violated).
    check("mono.jsonl", _snap_evt("r0", 1, 5, 5),
          _snap_evt("r0", 2, 2, 2, t=2.0))
    # snap sequence not strictly increasing.
    check("seq.jsonl", _snap_evt("r0", 2, 2, 2),
          _snap_evt("r0", 2, 5, 5, t=2.0))
    # sum shrank while count grew.
    check("sumdec.jsonl", _snap_evt("r0", 1, 2, 2, total=5.0),
          _snap_evt("r0", 2, 4, 4, total=1.0, t=2.0))


# -- Health / ops surface --------------------------------------------------


def test_healthz_and_ops_surface(monkeypatch):
    from stateright_tpu.explorer import Explorer

    _disarm(monkeypatch)
    monkeypatch.setenv("STpu_HIST", "1")
    monkeypatch.setenv("STpu_SLO", "1")
    c = TwoPhaseSys(3).checker().spawn_bfs().join()
    _disarm(monkeypatch)
    ex = Explorer(c)
    status, payload = ex.healthz()
    assert status == 200 and payload["healthy"]
    assert "host_bfs" in payload["participants"]

    # The small host run finishes in one worker block (one wave, no
    # gap yet): seed a couple of latency samples so the hist surface
    # has something to serve — the engine wiring itself is pinned by
    # test_armed_engine_snapshots_lint_export_summary.
    c._wave_obs.hist.observe("wave_latency_seconds", 0.004,
                             engine="host_bfs", kernel_path="none")
    c._wave_obs.hist.observe("wave_latency_seconds", 0.009,
                             engine="host_bfs", kernel_path="none")
    ops = ex.ops()
    part = ops["participants"]["host_bfs"]
    assert part["slo"]["healthy"]
    key = next(k for k in part["hist"]
               if k.startswith("wave_latency_seconds"))
    h = part["hist"][key]
    assert h["count"] >= 1 and h["p50"] in BUCKET_BOUNDS

    # /.metrics carries the histogram + SLO families live.
    metrics = ex.metrics()
    assert "stpu_wave_latency_seconds_bucket" in metrics
    assert "stpu_slo_healthy 1" in metrics

    # Force a breach: the health surface flips to 503.
    for _ in range(2 * MIN_SAMPLES):
        c._wave_obs.slo.observe("wave_success", ok=False)
    status, payload = ex.healthz()
    assert status == 503 and not payload["healthy"]
    assert not ex.ops()["healthy"]
    assert "stpu_slo_healthy 0" in ex.metrics()


def test_healthz_disarmed_still_200(monkeypatch):
    from stateright_tpu.explorer import Explorer

    _disarm(monkeypatch)
    c = TwoPhaseSys(3).checker().spawn_bfs().join()
    status, payload = Explorer(c).healthz()
    assert status == 200
    assert payload == {"healthy": True, "slo": "disarmed"}


# -- bench_compare ---------------------------------------------------------


def _bench_compare(*args):
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools",
                                      "bench_compare.py"), *args],
        capture_output=True, text=True)
    return proc.returncode, proc.stdout, proc.stderr


def test_bench_compare_rounds():
    r07 = os.path.join(_REPO, "BENCH_r07.json")
    r09 = os.path.join(_REPO, "BENCH_r09.json")
    rc, out, _ = _bench_compare(r07, r09)
    assert rc == 0
    assert "headline" in out and "value" in out
    assert "host_states_per_sec" in out
    # Reversed under a tight gate: the headline drop fails the run.
    rc, _, err = _bench_compare(r09, r07, "--max-regress", "2")
    assert rc == 1 and "FAIL" in err
    # --max-regress 0 disables the gate.
    rc, _, _ = _bench_compare(r09, r07, "--max-regress", "0")
    assert rc == 0
    # Trajectory mode over three rounds.
    rc, out, _ = _bench_compare(
        os.path.join(_REPO, "BENCH_r04.json"), r07, r09)
    assert rc == 0
    assert "r04" in out and "delta%" in out


# -- Service soak (slow) ---------------------------------------------------


@pytest.mark.slow
def test_service_soak_armed_observability(tmp_path, monkeypatch):
    """The acceptance soak: an armed job service under live traffic —
    /.healthz answers 200 and /.metrics serves _bucket/_sum/_count
    families MID-RUN, every job trace lints clean under v11, and the
    scheduler stats carry the SLO surface."""
    import service_client as sc

    from stateright_tpu.explorer import serve_service

    monkeypatch.setenv("STpu_HIST", "1")
    monkeypatch.setenv("STpu_SLO", "1")
    monkeypatch.setenv("STpu_ANOMALY", "1")
    monkeypatch.setenv("STpu_HIST_SNAP_S", "0.1")
    service, server = serve_service(
        addresses=("127.0.0.1", 0), block=False, workers=2,
        data_dir=str(tmp_path))
    host, port = server.server_address[:2]
    base = f"http://{host}:{port}"
    spec = {"model": "twopc", "params": {"rm_count": 3},
            "knobs": {"batch_size": 64}}
    try:
        ids = [sc.submit(base, spec)["id"] for _ in range(4)]
        # Mid-run: health + histogram families served live.
        health = sc.request(base, "/.healthz")
        assert health["healthy"]
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            states = {sc.status(base, j)["state"] for j in ids}
            metrics = sc.request(base, "/.metrics")
            if states == {"done"}:
                break
            time.sleep(0.1)
        assert states == {"done"}
        # After the jobs: job-latency families present and consistent.
        metrics = sc.request(base, "/.metrics")
        assert "stpu_job_latency_seconds_bucket" in metrics
        assert "stpu_job_latency_seconds_count" in metrics
        assert "stpu_slo_healthy 1" in metrics
        ops = sc.request(base, "/.ops")
        assert ops["healthy"] and "service" in ops["participants"]
        for j in ids:
            counts, errors = trace_lint.lint_file(
                service.trace_file(j))
            assert not errors, errors[:3]
    finally:
        server.shutdown()
        server.server_close()
        service.close()
