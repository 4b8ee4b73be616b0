"""Distributed observability units: relay/collector merge order, the
always-on flight recorder (armed cost, disarmed cost, dump format,
supervisor attachment), the v5 lint invariants, and the exporters on
synthetic merged streams.

The elastic end-to-end halves (merged kill/join drills linting clean,
worker-crash postmortems, straggler aggregates) live in
``tests/test_elastic.py`` where they share the module-scope runs; this
file is the cheap tier — synthetic events plus a couple of small
classic-engine runs.
"""

import json
import os
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_REPO, "examples"))
sys.path.insert(0, os.path.join(_REPO, "tools"))

from two_phase_commit import TwoPhaseSys  # noqa: E402

from stateright_tpu.obs import (FlightRecorder, NULL_RECORDER,  # noqa: E402
                                NullFlightRecorder, RelayTracer,
                                RunTracer, SCHEMA_VERSION,
                                TraceCollector, postmortem_path,
                                recorder_from_env, validate_event)
from stateright_tpu.obs.schema import WAVE_NULL_DEFAULTS  # noqa: E402

import trace_export  # noqa: E402
import trace_lint  # noqa: E402
import trace_summary  # noqa: E402


def _wave(i, *, states, unique, epoch=0, rnd=None, extra=None):
    evt = {"t": 1.0 + i, "states": states, "unique": unique,
           "bucket": 4, "waves": 1, "inflight": 0, "compiled": i == 0,
           "successors": 4, "candidates": 4, "novel": 2,
           # Real host-store occupancy gauges (schema v6 withdrew the
           # elastic producers' permanent-null allowance).
           "out_rows": 2, "capacity": 8,
           "load_factor": round(unique / 8, 4),
           "overflow": False, "bytes_per_state": 8, "arena_bytes": None,
           "table_bytes": 8 * unique, "epoch": epoch,
           "round": (i + 1 if rnd is None else rnd)}
    evt.update(extra or {})
    return evt


# -- RelayTracer -----------------------------------------------------------

def test_relay_tracer_stamps_and_rotates():
    relay = RelayTracer("w7", meta={"transport": "thread"})
    relay.wave(_wave(0, states=4, unique=2))
    relay.rotate({"reassigned_at_epoch": 1})
    relay.wave(_wave(0, states=3, unique=1, epoch=1, rnd=2))
    relay.close()
    batch, dropped = relay.drain(limit=100)
    assert dropped == 0
    assert [e["type"] for e in batch] == [
        "run_start", "wave", "run_end", "run_start", "wave", "run_end"]
    # Every event: worker-stamped, strictly increasing seq, valid.
    seqs = [e["seq"] for e in batch]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    for e in batch:
        assert e["worker"] == "w7"
        assert validate_event(e) == [], e
    # Rotation: a NEW run id, wave numbering restarts, seq does not.
    runs = [e["run"] for e in batch]
    assert runs[0] == runs[1] == runs[2] != runs[3]
    assert batch[1]["wave"] == 0 and batch[4]["wave"] == 0
    assert batch[1]["engine"] == "elastic_worker"


def test_relay_tracer_bounded_buffer_counts_drops(monkeypatch):
    monkeypatch.setattr(RelayTracer, "_CAPACITY", 4)
    relay = RelayTracer("w0")
    for i in range(10):
        relay.gauge("g", i)
    batch, dropped = relay.drain(limit=100)
    assert len(batch) == 4
    assert dropped == 7  # run_start + 6 gauges fell off the ring
    # Drain in bounded batches, FIFO.
    relay.gauge("g", 10)
    relay.gauge("g", 11)
    batch, _ = relay.drain(limit=1)
    assert len(batch) == 1 and batch[0]["value"] == 10


def test_relay_unbuffered_mirrors_to_flight():
    """relay_trace off (coordinator untraced): nothing queues for
    shipping, but the flight ring still sees every stamped event —
    dark runs keep their postmortems."""
    flight = FlightRecorder("w0", capacity=8)
    relay = RelayTracer("w0", buffering=False, mirror=flight.record)
    relay.wave(_wave(0, states=4, unique=2))
    batch, dropped = relay.drain()
    assert batch == [] and dropped == 0
    ring = flight.snapshot()
    assert [e["type"] for e in ring] == ["run_start", "wave"]
    assert ring[1]["worker"] == "w0"


# -- TraceCollector --------------------------------------------------------

def test_collector_merges_in_causal_order(tmp_path):
    """Batches arriving interleaved across workers come out sorted by
    (epoch, round, worker, seq), with rotation markers inheriting
    their worker's position (they must never sort ahead of the waves
    they follow)."""
    path = str(tmp_path / "merged.jsonl")
    tracer = RunTracer(path, "elastic")
    col = TraceCollector(tracer)

    r0, r1 = RelayTracer("w0"), RelayTracer("w1")
    r0.wave(_wave(0, states=4, unique=2))
    r0.wave(_wave(1, states=8, unique=4))
    r1.wave(_wave(0, states=5, unique=3))
    r1.rotate({})
    r1.wave(_wave(0, states=2, unique=1, epoch=1, rnd=3))
    # w1's batch lands FIRST: the merge must still put round-1 events
    # before round-2 before round-3, and w0 before w1 within a round.
    col.add_batch("w1", r1.drain(limit=100)[0])
    col.add_batch("w0", r0.drain(limit=100)[0])
    assert col.flush() > 0
    tracer.close()

    counts, errors = trace_lint.lint_file(path)
    assert errors == [], errors[:5]
    with open(path, encoding="utf-8") as f:
        events = [json.loads(line) for line in f if line.strip()]
    waves = [e for e in events if e["type"] == "wave"]
    assert [(w["round"], w["worker"]) for w in waves] == [
        (1, "w0"), (1, "w1"), (2, "w0"), (3, "w1")]
    # Per-worker seq order is preserved in file order.
    for worker in ("w0", "w1"):
        seqs = [e["seq"] for e in events
                if e.get("worker") == worker and "seq" in e]
        assert seqs == sorted(seqs)


def test_collector_straggler_attribution_math():
    col = TraceCollector(tracer=None)
    rec = col.straggler(5, 1, {
        "w0": {"compute_s": 0.4, "exchange_s": 0.1, "successors": 400,
               "queued": 30},
        "w1": {"compute_s": 0.1, "exchange_s": 0.0, "successors": 50,
               "queued": 10}})
    assert rec["slowest"] == "w0"
    assert rec["workers"]["w0"]["wait_s"] == 0.0
    assert rec["workers"]["w1"]["wait_s"] == pytest.approx(0.3)
    # wait share: 0.3 waited of 2 workers * 0.4 max = 0.375
    assert rec["wait_share"] == pytest.approx(0.375, abs=1e-4)
    assert rec["workers"]["w0"]["states_s"] == pytest.approx(1000.0)
    assert rec["workers"]["w0"]["load_share"] == pytest.approx(0.75)
    summary = col.summary()
    assert summary["rounds_timed"] == 1
    assert summary["max_wait_share"] == pytest.approx(0.375, abs=1e-4)
    assert summary["slowest"] == {"w0": 1}
    assert summary["workers"]["w1"]["wait_share"] == pytest.approx(0.75)


# -- Flight recorder -------------------------------------------------------

def test_flight_ring_bounded_and_dump_format(tmp_path):
    fl = FlightRecorder("unit", capacity=3, directory=str(tmp_path))
    for i in range(7):
        fl.record(_wave(i, states=4 * (i + 1), unique=2 * (i + 1)))
    fl.record_event("fault", point="wave_crash", hit=1, mode="crash")
    path = fl.dump("unit test reason")
    assert path == postmortem_path("unit", str(tmp_path))
    assert path == fl.last_dump
    with open(path, encoding="utf-8") as f:
        lines = [json.loads(line) for line in f if line.strip()]
    header, events = lines[0], lines[1:]
    assert header["type"] == "postmortem"
    assert header["reason"] == "unit test reason"
    assert header["events"] == len(events) == 3  # capacity bound
    # Bare entries were stamped into schema-valid wave events; the
    # recorded fault kept its own stamp; every line validates.
    for line in lines:
        assert validate_event(line) == [], line
    assert events[-1]["type"] == "fault"
    assert [e["states"] for e in events[:-1]] == [24, 28]  # newest kept


def test_flight_dump_lints_clean_and_never_clobbers(tmp_path):
    """A postmortem is a bounded WINDOW onto a failure: trace_lint
    accepts one even though its waves start mid-run and its last event
    is an unretired fault (the file's reason to exist) — and a second
    dump at the same name lands beside, not over, the first (a
    supervised retry's record must keep naming the file that
    describes THAT attempt)."""
    fl = FlightRecorder("coord", capacity=16, directory=str(tmp_path))
    # Interleave bare round entries with typed events, the coordinator
    # ring's actual shape — the bare ordinals are non-contiguous after
    # stamping, which only dump mode tolerates.
    for i in range(3):
        fl.record(_wave(i, states=4 * (i + 1), unique=2 * (i + 1)))
        fl.record_event("straggler", round=i + 1, epoch=0,
                        slowest="w0", wait_share=0.1, workers={})
    fl.record_event("fault", point="worker_crash", hit=1, mode="crash",
                    worker="w1")
    first = fl.dump("attempt 1")
    counts, errors = trace_lint.lint_file(first)
    assert errors == [], errors[:5]
    assert counts["postmortem"] == 1 and counts["fault"] == 1
    second = fl.dump("attempt 2")
    assert second != first and os.path.exists(first)
    with open(first, encoding="utf-8") as f:
        assert json.loads(f.readline())["reason"] == "attempt 1"
    with open(second, encoding="utf-8") as f:
        assert json.loads(f.readline())["reason"] == "attempt 2"


def test_relay_run_end_duration_is_per_run():
    relay = RelayTracer("w0")
    relay.rotate({})
    relay.close()
    batch, _ = relay.drain(limit=100)
    ends = [e for e in batch if e["type"] == "run_end"]
    assert len(ends) == 2
    # Both runs were (near-)instant; a cumulative-since-birth duration
    # bug would make the second include the first run's span.
    for e in ends:
        assert 0.0 <= e["dur"] < 1.0


def test_flight_disarmed_zero_cost(monkeypatch):
    """STpu_FLIGHT=0: the engines get the NULL_RECORDER singleton and
    the wave loop never calls into it — every null method is poisoned,
    mirroring the round-8 poisoned-null tracer test (zero recording,
    zero allocation when idle)."""
    monkeypatch.setenv("STpu_FLIGHT", "0")
    assert recorder_from_env("classic") is NULL_RECORDER

    def _boom(name):
        def poisoned(self, *a, **k):
            raise AssertionError(
                f"NullFlightRecorder.{name} called while disarmed")
        return poisoned

    for name in ("record", "record_event", "dump"):
        monkeypatch.setattr(NullFlightRecorder, name, _boom(name))

    c = TwoPhaseSys(3).checker().spawn_tpu_bfs(
        batch_size=64, fused=False).join()
    assert c._flight is NULL_RECORDER
    assert c.flight_dump is None
    assert c.unique_state_count() == 288


def test_flight_armed_by_default_records_waves(monkeypatch):
    """Default (env unset): the ring holds the engine's recent wave
    entries — the same dicts dispatch_log already owns, so recording
    allocates nothing extra — and a clean run dumps nothing."""
    monkeypatch.delenv("STpu_FLIGHT", raising=False)
    c = TwoPhaseSys(3).checker().spawn_tpu_bfs(
        batch_size=64, fused=False).join()
    assert c._flight.armed
    ring = c._flight.snapshot()
    assert 0 < len(ring) <= c._flight.capacity
    assert ring[-1]["states"] == c.state_count()
    assert ring[-1] is not c.dispatch_log[-1]  # snapshot stamps a copy
    assert c.flight_dump is None


def test_supervisor_attaches_flight_dump(tmp_path, monkeypatch):
    """A supervised engine crash leaves a postmortem and the retry
    record (and obs event) names it — the dark-run diagnosis path."""
    from stateright_tpu.resilience import Supervisor, reset_fault_plans

    monkeypatch.setenv("STpu_FLIGHT_DIR", str(tmp_path))
    monkeypatch.setenv("STpu_FAULTS", "wave_crash@n=2")
    reset_fault_plans()
    ckpt = str(tmp_path / "sup.npz")

    def factory(resume_from=None):
        return TwoPhaseSys(3).checker().spawn_tpu_bfs(
            batch_size=64, fused=False, checkpoint_path=ckpt,
            resume_from=resume_from)

    try:
        sup = Supervisor(factory, checkpoint_path=ckpt, max_retries=2,
                         backoff_s=0.01, sleep=lambda s: None)
        done = sup.run()
    finally:
        monkeypatch.delenv("STpu_FAULTS")
        reset_fault_plans()
    assert done.unique_state_count() == 288
    assert len(sup.recoveries) == 1
    dump = sup.recoveries[0]["dump"]
    assert dump and os.path.exists(dump)
    with open(dump, encoding="utf-8") as f:
        lines = [json.loads(line) for line in f if line.strip()]
    assert lines[0]["type"] == "postmortem"
    assert "InjectedFault" in lines[0]["reason"]
    assert any(e["type"] == "wave" for e in lines[1:])


# -- v5 lint invariants ----------------------------------------------------

def _evt(etype, **kw):
    base = {"type": etype, "schema_version": SCHEMA_VERSION,
            "engine": "elastic", "run": "r", "t": 1.0}
    base.update(kw)
    return json.dumps(base)


def _worker_wave(worker, seq, run="rw", **kw):
    fields = _wave(0, states=kw.pop("states", 4),
                   unique=kw.pop("unique", 2), rnd=kw.pop("rnd", 1))
    fields.update({"type": "wave", "schema_version": SCHEMA_VERSION,
                   "engine": "elastic_worker", "run": run,
                   "wave": kw.pop("wave", 0), "worker": worker,
                   "seq": seq})
    # The tracer stamps these for real producers; raw-JSON builders
    # stamp them here.
    for key in WAVE_NULL_DEFAULTS:
        fields.setdefault(key, None)
    fields.update(kw)
    return json.dumps(fields)


def test_lint_per_worker_seq_monotonicity():
    ok = [_worker_wave("w0", 1), _worker_wave("w0", 2, wave=1,
                                              states=8, unique=4)]
    _, errors = trace_lint.lint_lines(ok)
    assert not errors, errors
    # A seq regression is a merge-order loss, even across runs.
    bad = [_worker_wave("w0", 2), _worker_wave("w0", 1, run="rw2")]
    _, errors = trace_lint.lint_lines(bad)
    assert errors and "per-worker order lost" in errors[0]


def test_lint_elastic_wave_requires_attribution():
    line = json.loads(_worker_wave("w0", 1))
    line["worker"] = None
    _, errors = trace_lint.lint_lines([json.dumps(line)])
    assert any("without 'worker'" in e for e in errors)
    # Coordinator waves need their merge position too.
    coord = json.loads(_worker_wave("x", 1))
    coord.update(engine="elastic", worker=None, seq=None, epoch=None)
    _, errors = trace_lint.lint_lines([json.dumps(coord)])
    assert any("without 'epoch'" in e for e in errors)
    # v4 captures predate the keys: no retroactive failures.
    old = json.loads(_worker_wave("x", 1))
    old.update(engine="elastic", schema_version=4)
    for key in WAVE_NULL_DEFAULTS:
        old.pop(key, None)
    _, errors = trace_lint.lint_lines([json.dumps(old)])
    assert not errors, errors


@pytest.mark.parametrize("rounds,slots,ok", [
    (None, None, True), (3, 3 * 64, True), (0, 0, True),
    (3, None, False), (None, 64, False), (3, 2, False)])
def test_lint_probe_slots_match_rounds(rounds, slots, ok):
    """v17: probe slots are counted where the probe's rounds are, and
    every counted round carries at least one row."""
    line = _worker_wave("w0", 1, probe_rounds=rounds, probe_slots=slots)
    _, errors = trace_lint.lint_lines([line])
    assert (not errors) == ok, errors


#: the v19 wave keys, absent from older captures
V19_KEYS = ("sym_folded", "eventually_pending")


def test_lint_v16_wave_has_no_probe_slots():
    line = json.loads(_worker_wave("w0", 1))
    line["schema_version"] = 16
    for key in ("exchange_rounds",) + V19_KEYS:
        del line[key]
    _, errors = trace_lint.lint_lines([json.dumps(line)])
    assert any("probe_slots" in e for e in errors), errors
    del line["probe_slots"]
    _, errors = trace_lint.lint_lines([json.dumps(line)])
    assert not errors, errors


@pytest.mark.parametrize("rounds,slots,waves,ok", [
    (None, None, 1, True), (2, 2 * 3 * 8, 2, True), (3, 3 * 3 * 8, 2, True),
    (0, 0, 0, True), (2, None, 2, False), (None, 48, 2, False),
    (1, 24, 2, False)])
def test_lint_exchange_rounds_match_slots(rounds, slots, waves, ok):
    """v18: exchange rounds are counted where the exchange's slots are,
    and every wave of an exchanging dispatch runs at least one round."""
    line = _worker_wave("w0", 1, exchange_rounds=rounds,
                        exchange_slots=slots, waves=waves)
    _, errors = trace_lint.lint_lines([line])
    assert (not errors) == ok, errors


def test_lint_v17_wave_has_no_exchange_rounds():
    line = json.loads(_worker_wave("w0", 1))
    line["schema_version"] = 17
    for key in V19_KEYS:
        del line[key]
    _, errors = trace_lint.lint_lines([json.dumps(line)])
    assert any("exchange_rounds" in e for e in errors), errors
    del line["exchange_rounds"]
    _, errors = trace_lint.lint_lines([json.dumps(line)])
    assert not errors, errors


def test_lint_v18_wave_has_no_sym_counters():
    line = json.loads(_worker_wave("w0", 1))
    line["schema_version"] = 18
    _, errors = trace_lint.lint_lines([json.dumps(line)])
    assert any("sym_folded" in e for e in errors), errors
    for key in V19_KEYS:
        del line[key]
    _, errors = trace_lint.lint_lines([json.dumps(line)])
    assert not errors, errors


def test_lint_worker_fault_pairing_across_rotation():
    fault_w1 = _evt("fault", point="worker_crash", hit=1, mode="crash",
                    worker="w1")
    lost_w1 = _evt("worker_lost", worker="w1", epoch=0)
    migrated = _evt("migrate_done", partitions=4, to="w0", epoch=1)
    _, errors = trace_lint.lint_lines([fault_w1, lost_w1, migrated])
    assert not errors, errors
    # Unmigrated worker fault at end-of-stream: flagged per worker.
    _, errors = trace_lint.lint_lines([fault_w1, lost_w1])
    assert any("fault on worker 'w1'" in e for e in errors)
    # Two casualties cannot retire each other's faults: w1's
    # migrate_done must not silence w2's fault.
    fault_w2 = _evt("fault", point="worker_crash", hit=2, mode="crash",
                    worker="w2")
    lost_w2 = _evt("worker_lost", worker="w2", epoch=1)
    stream = [fault_w1, fault_w2, lost_w1, lost_w2, migrated]
    _, errors = trace_lint.lint_lines(stream)
    assert any("worker 'w2'" in e for e in errors)
    assert not any("worker 'w1'" in e and "fault" in e for e in errors)
    # The terminal abort retires everything (acknowledged, not silent).
    _, errors = trace_lint.lint_lines(
        stream + [_evt("abort", reason="gave up", attempts=1)])
    assert not errors, errors


# -- Exporters on merged streams -------------------------------------------

def test_export_one_track_per_worker(tmp_path):
    lines = [
        _evt("run_start", unix_t=0.0, meta={}),
        json.dumps(dict(json.loads(_worker_wave("w0", 1)))),
        json.dumps(dict(json.loads(_worker_wave("w1", 1, run="rx")))),
        # a rotated run for w0 must land on the SAME track
        json.dumps(dict(json.loads(
            _worker_wave("w0", 2, run="rw2", states=9, unique=5,
                         rnd=2)))),
        _evt("worker_lost", worker="w1", epoch=0),
        _evt("migrate_done", partitions=2, to="w0", epoch=1),
        _evt("straggler", round=1, epoch=0, slowest="w0",
             wait_share=0.25,
             workers={"w0": {"compute_s": 0.2, "wait_s": 0.0},
                      "w1": {"compute_s": 0.1, "wait_s": 0.1}}),
    ]
    path = tmp_path / "merged.jsonl"
    path.write_text("\n".join(lines) + "\n")
    chrome = trace_export.to_chrome(trace_export.load_events(str(path)))
    names = {e["args"]["name"] for e in chrome["traceEvents"]
             if e.get("name") == "process_name"}
    assert names == {"elastic coordinator", "elastic worker w0",
                     "elastic worker w1"}
    instants = {e["name"] for e in chrome["traceEvents"]
                if e.get("ph") == "i"}
    assert {"worker_lost", "migrate_done", "straggler"} <= instants
    prom = trace_export.to_prometheus(
        trace_export.load_events(str(path)))
    assert 'stpu_worker_wait_seconds_total{worker="w1"} 0.1' in prom
    assert "stpu_max_wait_share 0.25" in prom


def test_export_accepts_postmortem_dump(tmp_path):
    fl = FlightRecorder("w3", capacity=4, directory=str(tmp_path))
    fl.record(_wave(0, states=4, unique=2))
    fl.record_event("fault", point="worker_crash", hit=1, mode="crash",
                    worker="w3")
    dump = fl.dump("drill")
    events = trace_export.load_events(dump)
    chrome = trace_export.to_chrome(events)
    instants = {e["name"] for e in chrome["traceEvents"]
                if e.get("ph") == "i"}
    assert {"postmortem", "fault"} <= instants
    # And the summary CLI tabulates it.
    rows = trace_summary.summarize(events)
    assert rows["w3"]["faults"] == 1
