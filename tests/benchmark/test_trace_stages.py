"""The wave and stage readers (``benchmark/trace_stages.py``) and the
readers of the program's counters and host time.

Synthetic ops pin the arithmetic: a wave's edge is the start of its
top-level ``while`` in scope ``local_dedup``, and each stage's time is
the union of its ops over the span between the first and last edge, per
wave. A trace recorded on a TPU (``data/tpu_2pc4.xplane.pb``, cut by
``record_tpu_trace.py``: one fused 2pc-4 check at batch 64, with its
dispatch log in ``data/tpu_2pc4.json``) pins the rest against the real
thing: its edges are the check's waves, and the host's stats read of
each dispatch ends after that dispatch's program does, so the host
spans and the device ops share one clock.
"""

import json
import os

import pytest

from benchmark import run, trace_reduce, trace_stages

DATA = os.path.join(os.path.dirname(__file__), "data")
TPU_TRACE = os.path.join(DATA, "tpu_2pc4.xplane.pb")
METRICS = ("wave_device_ms", "load_ms", "properties_ms", "expand_ms",
           "fingerprint_ms", "local_dedup_ms", "probe_ms", "store_ms",
           "probe_rounds", "dedup_rounds", "host_ms_per_dispatch")


def _wave(t, expand=(10, 40), dedup=(40, 60), probe=(60, 90)):
    """One synthetic wave starting at ``t``: a top-level local-dedup
    loop with one op nested inside it, and two other stages."""
    return [(t + expand[0], t + expand[1], "fusion.1", "expand"),
            (t + dedup[0], t + dedup[1], "while.7", "local_dedup"),
            (t + dedup[0] + 5, t + dedup[1] - 5, "gather.3",
             "local_dedup"),
            (t + probe[0], t + probe[1], "while.9", "probe"),
            (t + probe[0] + 1, t + probe[0] + 3, "custom-call.2", None)]


@pytest.mark.parametrize("waves", [2, 3, 5])
def test_edges_and_per_wave_unions(waves):
    ops = [o for i in range(waves) for o in _wave(100 * i)]
    out = trace_stages.summarize(ops, [], (0, 100 * waves))
    assert out["edges"] == [100 * i + 40 for i in range(waves)]
    assert out["waves"] == waves - 1
    ms = 1e-6  # the synthetic times are ns
    # counted span: the first edge to the last; per wave, each stage's
    # union clipped to it (the nested op adds nothing to its loop)
    assert out["stage_ms"]["local_dedup"] == pytest.approx(20 * ms)
    assert out["stage_ms"]["probe"] == pytest.approx(30 * ms)
    assert out["stage_ms"]["expand"] == pytest.approx(30 * ms)
    assert out["stage_ms"]["load"] == 0
    assert out["wave_device_ms"] == pytest.approx(80 * ms)
    assert out["unscoped_ms"] == [["custom-call.2", pytest.approx(2 * ms)]]


def test_idle_named_by_the_innermost_program_span():
    ops = _wave(0) + _wave(200)
    spans = [(0, 300, "bench.wait"), (90, 250, "fused.process"),
             (95, 150, "fused.stats_wait")]
    out = trace_stages.summarize(ops, spans, (0, 300))
    # gaps [0, 10] and [290, 300] in the benchmark's sleep; [90, 210]
    # named by its middle, inside the stats wait
    assert out["idle_by_span"] == pytest.approx(
        {"bench.wait": 20e-9, "fused.stats_wait": 120e-9})
    assert trace_stages.span_label(spans, 120) == "fused.stats_wait"
    assert trace_stages.span_label(spans, 200) == "fused.process"
    assert trace_stages.span_label(spans, 280) == "bench.wait"
    assert trace_stages.span_label(spans, 400) == trace_stages.NO_SPAN
    assert sum(out["idle_by_span"].values()) == pytest.approx(
        (300 - 160) / 1e9)


def test_stage_of_takes_the_innermost_stage():
    assert trace_stages.stage_of(
        "jit(dispatch)/while/body/probe/while/body/gather") == "probe"
    assert trace_stages.stage_of("jit(dispatch)/while") is None
    assert trace_stages.stage_of("jit(f)/expanded/x") is None
    assert trace_stages.op_name("%while.93 = (s32[]) while(...)") == (
        "while.93")


@pytest.mark.parametrize("waves", [0, 1])
def test_below_two_edges_no_per_wave_numbers(waves):
    ops = [o for i in range(waves) for o in _wave(100 * i)]
    ops.append((500, 600, "fusion.4", None))
    out = trace_stages.summarize(ops, [], (0, 1000))
    assert out["waves"] == 0
    assert out["wave_device_ms"] is None
    assert set(out["stage_ms"].values()) == {None}


@pytest.fixture(scope="module")
def tpu():
    with open(os.path.join(DATA, "tpu_2pc4.json")) as f:
        meta = json.load(f)
    return trace_stages.read(TPU_TRACE), meta


def test_tpu_trace_edges_are_the_checks_waves(tpu):
    res, meta = tpu
    assert len(res["edges"]) == sum(e["waves"] for e in
                                    meta["dispatch_log"])
    covered = sum(res["stage_ms"].values())
    assert 0 < covered <= res["wave_device_ms"]
    assert max(res["stage_ms"], key=res["stage_ms"].get) == "probe"


def test_tpu_trace_paths_from_metadata_and_hlo():
    paths = trace_stages.op_paths(TPU_TRACE)
    loops = {trace_stages.stage_of(p) for op, p in paths.items()
             if op.startswith("%while")}
    # a while has no tf_op: these come from the program's HLO
    assert {"local_dedup", "probe"} <= loops
    assert sum(1 for p in paths.values() if p) > 0.8 * len(paths)


def test_tpu_trace_stats_wait_ends_after_its_dispatch(tpu):
    """The k-th ``fused.stats_wait`` returns the k-th dispatch's stats:
    it cannot end before that dispatch's program ends on the device."""
    from jax.profiler import ProfileData

    res, meta = tpu
    modules = []
    for plane in ProfileData.from_file(TPU_TRACE).planes:
        if plane.name.startswith(trace_reduce.TPU_PLANE):
            modules += [(ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in trace_reduce._events(plane,
                                                       "XLA Modules")
                        if ev.name.startswith("jit_dispatch(")]
    waits = sorted(s for s in res["spans"] if s[2] == "fused.stats_wait")
    assert len(modules) == len(waits) == len(meta["dispatch_log"])
    for (_, end), (_, wait_end, _) in zip(sorted(modules), waits):
        assert wait_end >= end


def _ctx(tmp_path, entries, trace=True):
    return {"window": {"entries": entries},
            "trace": {"idle_share": 0.0} if trace else None,
            "out_dir": str(tmp_path)}


def test_counter_readers(tmp_path):
    entries = [{"waves": 16, "probe_rounds": 20, "dedup_rounds": 40,
                "host_s": 0.002},
               {"waves": 4, "probe_rounds": 5, "dedup_rounds": 10,
                "host_s": 0.004}]
    ctx = _ctx(tmp_path, entries)
    read = {m: run.load_plugin("metrics", m).read for m in METRICS}
    assert read["probe_rounds"](ctx) == 25 / 20
    assert read["dedup_rounds"](ctx) == 50 / 20
    assert read["host_ms_per_dispatch"](ctx) == pytest.approx(3.0)


def test_readers_return_none_without_the_program_s_marks(tmp_path):
    """A program without the counters, or a run whose trace has no
    stretch, reports none of the new metrics and raises nothing."""
    older = [{"waves": 16, "candidates": 10}]
    read = {m: run.load_plugin("metrics", m).read for m in METRICS}
    for trace in (True, False):
        ctx = _ctx(tmp_path, older, trace)
        assert {m: read[m](ctx) for m in METRICS} == dict.fromkeys(METRICS)


def test_stage_readers_read_the_runs_trace(tmp_path):
    trace_dir = tmp_path / "trace"
    trace_dir.mkdir()
    with open(TPU_TRACE, "rb") as f:
        (trace_dir / "t.xplane.pb").write_bytes(f.read())
    ctx = _ctx(tmp_path, [])
    res = trace_stages.read(str(trace_dir / "t.xplane.pb"))
    for m in METRICS[:8]:
        got = run.load_plugin("metrics", m).read(ctx)
        want = (res["wave_device_ms"] if m == "wave_device_ms"
                else res["stage_ms"][m[:-3]])
        assert got == want and got is not None
