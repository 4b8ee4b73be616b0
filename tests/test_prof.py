"""Continuous wave profiler (stateright_tpu/obs/prof.py + schema v13).

Contracts pinned here:

- **v13 events validate and lint**: ``profile_snapshot`` events pass
  ``validate_event`` and ``trace_lint``'s v13 invariants (per-run
  strictly increasing ``snap``, finite positive ``measured_s`` /
  ``cost_ratio``, ``intensity == flops/bytes``); corrupted variants
  are rejected. Old v12 wave captures (no cost fields) still validate.
- **One cost surface, every engine**: all four device engines, armed
  (``STpu_PROF=1``), stamp the three nullable cost fields on every
  wave event with the exact v13 field set, capture XLA's own
  ``cost_analysis()`` flops/bytes for every compiled program, and emit
  at least one ``profile_snapshot`` with a finite ``cost_ratio`` per
  program — and arming changes no checking result.
- **Disarmed means free**: ``STpu_PROF`` unset gets the shared
  ``NULL_PROF`` singleton and the wave loop never calls into it (every
  null method is poisoned) — one attribute check per dispatch, zero
  cost lookups.
- **Deterministic cadence**: ``should_sample`` is a pure function of
  the dispatch sequence — every Nth dispatch plus the first dispatch
  of each new program key.
- **Per-arm A/B attribution**: the matmul-vs-step A/B captures a
  distinct cost model per arm (the prof key prefix encodes the active
  plan), with identical checking results.
"""

import json
import math
import os
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_REPO, "examples"))

from two_phase_commit import TwoPhaseSys  # noqa: E402

from stateright_tpu.obs import validate_event  # noqa: E402
from stateright_tpu.obs.prof import (NULL_PROF, NullWaveProfiler,
                                     WaveProfiler, clear_program_records,
                                     prof_from_env,
                                     prometheus_prof_lines)  # noqa: E402

sys.path.insert(0, os.path.join(_REPO, "tools"))
import trace_lint  # noqa: E402

ENGINES = ("classic", "fused", "sharded", "sharded_fused")


def _spawn(model, engine, **kw):
    b = model.checker()
    if engine == "classic":
        return b.spawn_tpu_bfs(batch_size=64, fused=False, **kw)
    if engine == "fused":
        return b.spawn_tpu_bfs(batch_size=64, fused=True, **kw)
    if engine == "sharded":
        return b.spawn_tpu_bfs(batch_size=32, sharded=True, fused=False,
                               **kw)
    assert engine == "sharded_fused"
    return b.spawn_tpu_bfs(batch_size=32, sharded=True, **kw)


@pytest.fixture(autouse=True)
def _fresh_cost_table():
    # The static cost table is process-wide by design; isolate tests.
    clear_program_records()
    yield
    clear_program_records()


def _events(path):
    with open(path, encoding="utf-8") as f:
        return [json.loads(line) for line in f if line.strip()]


# -- v13 schema + lint units ------------------------------------------------

_START = {"schema_version": 13, "engine": "classic", "run": "r-0",
          "type": "run_start", "t": 1.0, "unix_t": 1.0, "meta": {}}

#: A known-good snapshot (field values from a real classic 2pc
#: capture); intensity == flops / bytes to the lint tolerance.
_SNAP = {"schema_version": 13, "engine": "classic", "run": "r-0",
         "type": "profile_snapshot", "t": 1.5,
         "flops": 193085.0, "bytes": 1494572.0, "peak_bytes": 1109737,
         "flops_per_s": 92284493.494, "bytes_per_s": 714326954.502,
         "intensity": 0.129191, "key": "classic|aa|(64, 65536, 768)",
         "kernel_path": "xla", "expand_impl": "step", "snap": 1,
         "measured_s": 0.002092, "cost_ratio": 1.0}


def _lint(tmp_path, events, name="t.jsonl"):
    p = tmp_path / name
    p.write_text("\n".join(json.dumps(e) for e in events) + "\n",
                 encoding="utf-8")
    return trace_lint.lint_file(str(p))


def test_profile_snapshot_validates():
    assert validate_event(_SNAP) == []
    # Null-cost snapshots (lazy-jit programs) are legal: the roofline
    # gauges are nullable, the measurement fields are not.
    nulled = dict(_SNAP, flops=None, bytes=None, peak_bytes=None,
                  flops_per_s=None, bytes_per_s=None, intensity=None)
    assert validate_event(nulled) == []
    assert validate_event({k: v for k, v in _SNAP.items()
                           if k != "key"}) != []
    assert validate_event(dict(_SNAP, cost_ratio="fast")) != []


def test_lint_accepts_good_snapshot_stream(tmp_path):
    snap2 = dict(_SNAP, snap=2, t=1.6, measured_s=0.0011,
                 cost_ratio=0.525812, flops_per_s=175531818.182,
                 bytes_per_s=1358701818.182)
    counts, errors = _lint(tmp_path, [_START, _SNAP, snap2])
    assert errors == []
    assert counts["profile_snapshot"] == 2


@pytest.mark.parametrize("bad, expect", [
    (dict(_SNAP, snap=2, t=1.4), "snap"),          # then snap=1 below
    (dict(_SNAP, measured_s=-0.001), "measured_s"),
    (dict(_SNAP, measured_s=0.0), "measured_s"),
    (dict(_SNAP, cost_ratio=float("inf")), "cost_ratio"),
    (dict(_SNAP, intensity=0.5), "intensity"),
])
def test_lint_rejects_bad_snapshots(tmp_path, bad, expect):
    events = ([_START, bad, _SNAP] if expect == "snap"
              else [_START, bad])
    _, errors = _lint(tmp_path, events)
    assert errors, bad
    assert any(expect in e for e in errors), (expect, errors)


def test_v12_wave_capture_still_validates():
    """A pre-profiler capture (schema 12, no cost fields) must keep
    linting clean — and a v13 wave must carry the cost fields."""
    from stateright_tpu.obs.schema import WAVE_FIELDS_V12, WAVE_FIELDS_V14

    v13 = {k: None for k in WAVE_FIELDS_V14}  # the v13-v14 field set
    v13.update({"schema_version": 13, "engine": "classic", "run": "r",
                "type": "wave", "t": 1.0, "wave": 0, "states": 1,
                "unique": 1, "bucket": 64, "waves": 1, "inflight": 0,
                "compiled": False, "successors": 0, "candidates": 0,
                "novel": 0, "capacity": 64, "overflow": False,
                "rows": 1, "out_rows": 64, "io_stall_s": 0.0})
    assert validate_event(v13) == []
    v12 = {k: v for k, v in v13.items()
           if k in WAVE_FIELDS_V12 or k in ("schema_version", "engine",
                                            "run", "type", "t")}
    v12["schema_version"] = 12
    assert validate_event(v12) == []
    # Exact field set both directions: a v13 wave MISSING the cost
    # fields is invalid, as is a v12 wave carrying them.
    assert validate_event(dict(v12, schema_version=13)) != []
    assert validate_event(dict(v12, schema_version=12,
                               cost_flops=1.0)) != []


# -- Armed: every engine ----------------------------------------------------

def test_cost_capture_across_engines(tmp_path, monkeypatch):
    """All four device engines, armed with per-dispatch sampling: v13
    traces lint clean, every wave carries the exact field set, every
    compiled program's snapshots have XLA cost-model flops/bytes and a
    finite positive cost_ratio — and arming changes no result."""
    from stateright_tpu.obs.schema import WAVE_FIELDS

    model = TwoPhaseSys(3)
    ref = model.checker().spawn_bfs().join()  # disarmed reference
    for engine in ENGINES:
        clear_program_records()
        path = tmp_path / f"{engine}.jsonl"
        monkeypatch.setenv("STpu_TRACE", str(path))
        monkeypatch.setenv("STpu_PROF", "1")
        monkeypatch.setenv("STpu_PROF_SAMPLE", "1")
        c = _spawn(model, engine).join()
        monkeypatch.delenv("STpu_TRACE")

        assert c.unique_state_count() == ref.unique_state_count(), engine
        assert c.state_count() == ref.state_count(), engine
        assert set(c.discoveries()) == set(ref.discoveries()), engine

        _, errors = trace_lint.lint_file(str(path))
        assert errors == [], (engine, errors[:3])
        events = _events(path)
        waves = [e for e in events if e.get("type") == "wave"]
        snaps = [e for e in events
                 if e.get("type") == "profile_snapshot"]
        assert waves and snaps, engine
        assert {frozenset(w) for w in waves} == {frozenset(WAVE_FIELDS)}
        # Sampled every dispatch: every wave carries a measured ratio
        # and the statically captured program cost.
        for w in waves:
            assert w["cost_flops"] and w["cost_flops"] > 0, (engine, w)
            assert w["cost_bytes"] and w["cost_bytes"] > 0, (engine, w)
            assert (w["cost_ratio"] is not None
                    and math.isfinite(w["cost_ratio"])
                    and w["cost_ratio"] > 0), (engine, w)
        for s in snaps:
            assert s["flops"] and s["flops"] > 0, (engine, s)
            assert s["intensity"] == pytest.approx(
                s["flops"] / s["bytes"], rel=1e-3), engine
        # The live stats surface mirrors the stream.
        prof = c.scheduler_stats()["prof"]
        assert prof["sampled"] == len(snaps), engine
        assert prof["dispatches"] >= prof["sampled"], engine
        assert set(prof["programs"]) == {s["key"] for s in snaps}, engine
        # And it renders as the stpu_prof_* exposition families.
        lines = prometheus_prof_lines(prof, engine)
        assert any(line.startswith("stpu_prof_flops{") for line in lines)


# -- Disarmed: poisoned null ------------------------------------------------

def test_disarmed_prof_is_shared_null_and_never_called(monkeypatch):
    """STpu_PROF unset: the engines hold the NULL_PROF singleton and
    the wave loop never calls into it — every null method is poisoned,
    so a single stray cost lookup in the hot loop fails the run."""
    monkeypatch.delenv("STpu_PROF", raising=False)
    assert prof_from_env("classic") is NULL_PROF

    def _boom(name):
        def poisoned(self, *a, **k):
            raise AssertionError(
                f"NullWaveProfiler.{name} called with profiling "
                "disarmed")
        return poisoned

    for name in ("capture", "should_sample", "wave", "stats", "close"):
        monkeypatch.setattr(NullWaveProfiler, name, _boom(name))
    c = _spawn(TwoPhaseSys(3), "classic").join()
    assert c.unique_state_count() > 0
    assert c.scheduler_stats()["prof"] is None
    # Disarmed waves carry no cost fields at all (they are stamped by
    # the collector as nulls only when some OTHER producer is armed).
    assert all("cost_flops" not in e or e["cost_flops"] is None
               for e in c.dispatch_log)


# -- Sampling cadence -------------------------------------------------------

def test_sampling_cadence_deterministic():
    seq = ["k1"] * 6 + ["k2"] + ["k1"] * 5
    pa, pb = WaveProfiler("a", 4), WaveProfiler("b", 4)
    a = [pa.should_sample(k) for k in seq]
    b = [pb.should_sample(k) for k in seq]
    assert a == b  # same dispatch sequence, same sampled set
    # Every Nth dispatch (0, 4, 8) plus the first of each new key (k2
    # at index 6).
    assert a == [i % 4 == 0 or i == 6 for i in range(len(seq))]
    assert pa.stats()["dispatches"] == len(seq)


# -- Matmul-vs-step A/B: per-arm cost capture -------------------------------

def _matmul_ab(model, engine):
    arms = {}
    for on in (True, False):
        clear_program_records()
        c = _spawn(model, engine, wave_matmul=on).join()
        prof = c.scheduler_stats()["prof"]
        assert prof is not None and prof["programs"], on
        for key, snap in prof["programs"].items():
            assert snap["flops"] and snap["flops"] > 0, (on, key)
            assert snap["bytes"] and snap["bytes"] > 0, (on, key)
            assert math.isfinite(snap["cost_ratio"]), (on, key)
        arms[on] = (c.state_count(), c.unique_state_count(),
                    tuple(sorted(c.discoveries())),
                    frozenset(prof["programs"]))
    # Identical results; DISTINCT cost models (the prof key prefix
    # encodes whether the matmul plan was compiled in).
    assert arms[True][:3] == arms[False][:3]
    assert arms[True][3].isdisjoint(arms[False][3])


def test_matmul_vs_step_ab_captures_both_arms(monkeypatch):
    monkeypatch.setenv("STpu_PROF", "1")
    monkeypatch.setenv("STpu_PROF_SAMPLE", "1")
    _matmul_ab(TwoPhaseSys(3), "classic")


@pytest.mark.slow
def test_matmul_vs_step_ab_increment_fused(monkeypatch):
    from increment import IncrementModel

    monkeypatch.setenv("STpu_PROF", "1")
    monkeypatch.setenv("STpu_PROF_SAMPLE", "1")
    _matmul_ab(IncrementModel(3), "fused")
    _matmul_ab(TwoPhaseSys(4), "fused")
