"""The run-telemetry subsystem (stateright_tpu/obs + tools/trace_*).

Contracts pinned here:

- **One wave schema, every engine**: all four device engines AND the
  host BFS emit wave events with the exact same field set for the same
  2pc run, schema-validated by ``tools/trace_lint.py``'s validator —
  one consumer, no per-engine parsers.
- **Disabled means free**: with ``STpu_TRACE`` unset the engines hold
  the shared ``NULL_TRACER`` singleton and the wave loop NEVER calls
  into it (the null methods are poisoned for the test) — the disabled
  subsystem is one attribute check, zero events, zero allocations.
- **Telemetry never changes discovery results**: traced and untraced
  runs produce identical counts and discovery sets (the bit-identity
  contract; the wider 4-engine parity suites are the main guard).
- **Tooling round trip**: a capture lints clean (this is the tier-1
  wiring of trace_lint), exports to a Chrome/Perfetto trace, and dumps
  Prometheus text.
"""

import io
import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_REPO, "examples"))

from two_phase_commit import TwoPhaseSys  # noqa: E402

from stateright_tpu.obs import (NULL_TRACER, SCHEMA_VERSION, WAVE_FIELDS,
                                NullTracer, RunTracer, tracer_from_env,
                                validate_event)  # noqa: E402

sys.path.insert(0, os.path.join(_REPO, "tools"))
import trace_export  # noqa: E402
import trace_lint  # noqa: E402


def _events(path):
    with open(path, encoding="utf-8") as f:
        return [json.loads(line) for line in f if line.strip()]


def _spawn(model, engine):
    b = model.checker()
    if engine == "host_bfs":
        return b.spawn_bfs()
    if engine == "classic":
        return b.spawn_tpu_bfs(batch_size=64, fused=False)
    if engine == "fused":
        return b.spawn_tpu_bfs(batch_size=64, fused=True)
    if engine == "sharded":
        return b.spawn_tpu_bfs(batch_size=32, sharded=True, fused=False)
    assert engine == "sharded_fused"
    return b.spawn_tpu_bfs(batch_size=32, sharded=True)


ENGINES = ("host_bfs", "classic", "fused", "sharded", "sharded_fused")


def test_wave_schema_identical_across_engines(tmp_path, monkeypatch):
    """All four device engines + host BFS: same 2pc run, same wave
    field set, schema-valid stream, counts consistent with the
    checker's own totals — and tracing changes no result."""
    model = TwoPhaseSys(3)
    ref = model.checker().spawn_bfs().join()  # untraced reference
    field_sets = {}
    for engine in ENGINES:
        path = tmp_path / f"{engine}.jsonl"
        monkeypatch.setenv("STpu_TRACE", str(path))
        c = _spawn(model, engine).join()
        monkeypatch.delenv("STpu_TRACE")

        # Telemetry must not perturb checking.
        assert c.unique_state_count() == ref.unique_state_count(), engine
        assert c.state_count() == ref.state_count(), engine
        assert set(c.discoveries()) == set(ref.discoveries()), engine

        counts, errors = trace_lint.lint_file(str(path))
        assert errors == [], (engine, errors[:3])
        events = _events(path)
        waves = [e for e in events if e.get("type") == "wave"]
        assert waves, engine
        assert all(e["engine"] == engine for e in waves)
        assert {e["type"] for e in events} >= {"run_start", "wave",
                                               "run_end"}
        field_sets[engine] = {frozenset(w) for w in waves}
        # Cumulative totals on the last wave match the checker.
        assert waves[-1]["states"] == c.state_count(), engine
        assert waves[-1]["unique"] == c.unique_state_count(), engine
        # Per-dispatch deltas fold back to the totals.
        assert (sum(w["successors"] for w in waves)
                == c.state_count() - 1), engine
        assert (sum(w["novel"] for w in waves)
                == c.unique_state_count() - 1), engine

    # THE schema contract: one exact field set, every engine.
    expected = {frozenset(WAVE_FIELDS)}
    for engine, sets in field_sets.items():
        assert sets == expected, (engine, sets)


def test_trace_disabled_zero_events_zero_allocations(monkeypatch):
    """STpu_TRACE unset: the engines get the NULL_TRACER singleton and
    the wave loop never emits into it — every null emitter is poisoned,
    so a single stray emit (= a single stray event-dict allocation in
    the hot loop) fails the run. ``span`` is exempt: the fused host loop
    opens one per dispatch turn without a guard, since it is also the
    profiler annotation (recording nothing outside a session)."""
    monkeypatch.delenv("STpu_TRACE", raising=False)
    assert tracer_from_env("classic") is NULL_TRACER

    def _boom(name):
        def poisoned(self, *a, **k):
            raise AssertionError(
                f"NullTracer.{name} called with tracing disabled")
        return poisoned

    emitters = ("wave", "event", "counter", "gauge", "span_event",
                "emit_raw")
    for name in emitters:
        monkeypatch.setattr(NullTracer, name, _boom(name))
    exempt = {n for n in dir(NullTracer) if not n.startswith("_")
              and callable(getattr(NullTracer, n))} - set(emitters)
    assert exempt == {"span", "close"}  # close ends the run, not a wave
    opened = []
    span = NullTracer.span
    monkeypatch.setattr(NullTracer, "span",
                        lambda self, name, **a: (opened.append(name),
                                                 span(self, name, **a))[1])

    model = TwoPhaseSys(3)
    c = model.checker().spawn_tpu_bfs(batch_size=64, fused=False).join()
    assert c._tracer is NULL_TRACER
    fused = model.checker().spawn_tpu_bfs(batch_size=64).join()
    assert fused._tracer is NULL_TRACER
    assert {"fused.launch", "fused.process", "fused.stats_wait"} <= set(
        opened)
    host = model.checker().spawn_bfs().join()
    assert host._tracer is NULL_TRACER
    assert c.unique_state_count() == host.unique_state_count()
    assert fused.unique_state_count() == host.unique_state_count()


def test_tracer_spans_counters_nested(tmp_path):
    tr = RunTracer(str(tmp_path / "t.jsonl"), "bench", meta={"k": 1})
    with tr.span("outer"):
        with tr.span("inner", detail="x"):
            pass
    tr.counter("widgets", 2)
    tr.counter("widgets", 3)
    tr.gauge("pressure", 0.5)
    tr.close()
    tr.close()  # idempotent
    events = _events(tmp_path / "t.jsonl")
    assert [e["type"] for e in events] == [
        "run_start", "span", "span", "counter", "counter", "gauge",
        "run_end"]
    for e in events:
        assert validate_event(e) == [], e
        assert e["schema_version"] == SCHEMA_VERSION
    inner, outer = events[1], events[2]  # inner closes first
    assert (inner["name"], inner["depth"]) == ("inner", 1)
    assert (outer["name"], outer["depth"]) == ("outer", 0)
    assert inner["attrs"] == {"detail": "x"}
    assert outer["dur"] >= inner["dur"]
    assert events[4]["value"] == 5  # counter accumulates
    assert events[-1]["counters"] == {"widgets": 5}


def test_trace_lint_cli(tmp_path, monkeypatch):
    """trace_lint runs standalone (the tier-1 wiring) on an engine
    capture, and actually rejects malformed streams."""
    path = tmp_path / "run.jsonl"
    monkeypatch.setenv("STpu_TRACE", str(path))
    _spawn(TwoPhaseSys(3), "classic").join()
    monkeypatch.delenv("STpu_TRACE")
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "trace_lint.py"),
         str(path)], capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "OK" in proc.stdout

    # Corruption trips it: a wave missing a schema field.
    bad = tmp_path / "bad.jsonl"
    events = _events(path)
    wave = next(e for e in events if e.get("type") == "wave").copy()
    del wave["load_factor"]
    wave["rider"] = 1
    bad.write_text(json.dumps(wave) + "\nnot json\n")
    counts, errors = trace_lint.lint_file(str(bad))
    assert any("load_factor" in e for e in errors)
    assert any("rider" in e for e in errors)
    assert any("invalid JSON" in e for e in errors)


def test_trace_export_chrome_and_prometheus(tmp_path, monkeypatch):
    path = tmp_path / "run.jsonl"
    monkeypatch.setenv("STpu_TRACE", str(path))
    c = _spawn(TwoPhaseSys(3), "fused").join()
    monkeypatch.delenv("STpu_TRACE")
    out = tmp_path / "run.chrome.json"
    prom = tmp_path / "run.prom"
    rc = trace_export.main([str(path), "-o", str(out),
                            "--prom", str(prom)])
    assert rc == 0
    chrome = json.loads(out.read_text())
    evs = chrome["traceEvents"]
    assert evs and {"ph", "pid", "name"} <= set(evs[0])
    slices = [e for e in evs if e["ph"] == "X"]
    assert slices and all(e["dur"] >= 0 and e["ts"] >= 0
                          for e in slices)
    assert any(e["ph"] == "C" for e in evs)  # counter tracks
    text = prom.read_text()
    assert f"stpu_states_total{{engine=\"fused\"" in text
    assert str(c.state_count()) in text


def test_untyped_event_is_rejected():
    """Every event carries a ``type``; the retired session family's
    untyped ``{"event": ...}`` lines no longer validate."""
    errors = validate_event({"event": "init", "platform": "cpu",
                             "schema_version": SCHEMA_VERSION,
                             "t": 1.0, "unix_t": 2.0})
    assert errors and "unknown type" in errors[0]


def test_report_flushes_and_prints_rate():
    class FlushCounting(io.StringIO):
        flushes = 0

        def flush(self):
            self.flushes += 1
            super().flush()

    from stateright_tpu.test_util import LinearEquation

    w = FlushCounting()
    (LinearEquation(2, 10, 14).checker().spawn_bfs()
     .report(w, period_s=0.01))
    out = w.getvalue()
    assert out.startswith("Done. states=15, unique=12, sec=")
    assert "states/s=" in out
    assert w.flushes >= 1


def test_metrics_endpoint_prometheus():
    """GET /.metrics serves live Prometheus text for any checker; with
    a device engine it includes load factor + wave cadence."""
    from stateright_tpu.explorer import Explorer

    c = TwoPhaseSys(3).checker().spawn_tpu_bfs(
        batch_size=64, fused=False).join()
    text = Explorer(c).metrics()
    metrics = {}
    for line in text.splitlines():
        if line and not line.startswith("#"):
            name, value = line.rsplit(" ", 1)
            metrics[name] = float(value)
    assert metrics["stpu_states_total"] == c.state_count()
    assert metrics["stpu_unique_states_total"] == c.unique_state_count()
    assert metrics["stpu_done"] == 1.0
    assert 0.0 < metrics["stpu_table_load_factor"] <= 0.5
    assert metrics["stpu_waves_total"] == len(c.dispatch_log)
    assert "stpu_wave_seconds" in metrics


def test_overflow_and_grow_events(tmp_path, monkeypatch):
    """A forced-overflow run records overflow_redispatch events AND the
    per-wave overflow flag; growth shows up as grow events; and
    scheduler_stats — a view over the same stream — agrees."""
    from stateright_tpu.tpu.engine import TpuBfsChecker

    monkeypatch.setattr(
        TpuBfsChecker, "_pick_out_rows",
        lambda self, B: 8 if self._succ_ladder_on
        else self._succ_full_rows(B))
    path = tmp_path / "overflow.jsonl"
    monkeypatch.setenv("STpu_TRACE", str(path))
    c = TwoPhaseSys(4).checker().spawn_tpu_bfs(
        batch_size=64, fused=False, table_capacity=1 << 12).join()
    monkeypatch.delenv("STpu_TRACE")
    events = _events(path)
    overflows = [e for e in events
                 if e.get("type") == "overflow_redispatch"]
    assert overflows
    flagged = sum(1 for e in events
                  if e.get("type") == "wave" and e["overflow"])
    assert flagged == len(overflows)
    stats = c.scheduler_stats()
    assert stats["succ_ladder"]["overflow_redispatches"] == flagged
    assert any(e.get("type") == "grow" for e in events), \
        "2pc-4 at 2^12 must grow the table at least once"


def test_schema_v6_field_map_excludes_v8_keys():
    """A v6 wave with v8 riders is NOT valid, and a v8 wave missing
    them is NOT valid — additions go through the version bump, one
    schema per version."""
    from stateright_tpu.obs.schema import (WAVE_FIELDS, WAVE_FIELDS_V6,
                                           validate_event)

    assert "kernel_path" not in WAVE_FIELDS_V6
    assert "rows" not in WAVE_FIELDS_V6
    base = {"type": "wave", "schema_version": 6, "engine": "classic",
            "run": "x", "wave": 0, "t": 1.0}
    for k in WAVE_FIELDS_V6:
        base.setdefault(k, None)
    base.update(states=1, unique=1, bucket=4, waves=1, inflight=0,
                compiled=False, successors=0, candidates=0, novel=0,
                overflow=False)
    assert validate_event(base) == []
    bad = dict(base, kernel_path="xla", rows=4)
    assert any("unexpected" in e for e in validate_event(bad))
    v8 = dict(base, schema_version=8)
    assert any("missing field 'kernel_path'" in e
               for e in validate_event(v8))
    assert validate_event(dict(v8, kernel_path=None, rows=None)) == []


def test_schema_v11_field_map_excludes_v12_keys():
    """A v11 wave with the v12 rider is NOT valid, and a v12 wave
    missing it is NOT valid — additions go through the version bump,
    one schema per version."""
    from stateright_tpu.obs.schema import (WAVE_FIELDS, WAVE_FIELDS_V11,
                                           validate_event)

    assert "expand_impl" not in WAVE_FIELDS_V11
    assert "expand_impl" in WAVE_FIELDS
    base = {"type": "wave", "schema_version": 11, "engine": "classic",
            "run": "x", "wave": 0, "t": 1.0}
    for k in WAVE_FIELDS_V11:
        base.setdefault(k, None)
    base.update(states=1, unique=1, bucket=4, waves=1, inflight=0,
                compiled=False, successors=0, candidates=0, novel=0,
                overflow=False)
    assert validate_event(base) == []
    bad = dict(base, expand_impl="step")
    assert any("unexpected" in e for e in validate_event(bad))
    v12 = dict(base, schema_version=12)
    assert any("missing field 'expand_impl'" in e
               for e in validate_event(v12))
    assert validate_event(dict(v12, expand_impl=None)) == []
