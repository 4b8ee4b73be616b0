"""The trace reduction and the peaks table, on a trace recorded on the
CPU (``data/cpu_trace.xplane.pb``: three rounds of a 10 ms
``bench.wait`` span and two jitted calls, a sort and a cumulative sum,
inside one ``bench.traced`` span). On the CPU the operations run on
the PjRt client's thread of the host plane; the expected numbers were
read off the file by hand (the three ``sort.0`` events last 11,161,500,
10,231,937 and 10,039,045 ns)."""

import os

import pytest

from benchmark import costs, trace_reduce

TRACE = os.path.join(os.path.dirname(__file__), "data",
                     "cpu_trace.xplane.pb")
CPU = {"device_plane": "/host:CPU", "op_line": "tf_XLAPjRtCpuClient"}


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce(TRACE, **CPU)


def test_union_merges_overlaps_and_clips():
    ivs = [(5, 9), (0, 2), (1, 3), (8, 12), (20, 21)]
    assert trace_reduce.union(ivs) == [(0, 3), (5, 12), (20, 21)]
    assert trace_reduce.clip(trace_reduce.union(ivs), 2, 20) == [
        (2, 3), (5, 12)]


def test_window_is_the_traced_span(reduced):
    assert reduced["window_s"] == pytest.approx(0.069685591, abs=1e-9)


def test_busy_is_the_union_of_operations(reduced):
    sorts = (11_161_500 + 10_231_937 + 10_039_045) / 1e9
    assert reduced["busy_s"] == pytest.approx(0.032675123, abs=1e-9)
    assert sorts < reduced["busy_s"] < reduced["window_s"]
    assert reduced["idle_share"] == pytest.approx(
        1 - reduced["busy_s"] / reduced["window_s"])
    assert reduced["devices"] == 1


def test_top_ops_by_time(reduced):
    name, seconds = reduced["device_ops"][0]
    assert name == "sort.0"
    assert seconds == pytest.approx(0.031432482, abs=1e-9)
    times = [t for _, t in reduced["device_ops"]]
    assert times == sorted(times, reverse=True) and len(times) <= 10


def test_idle_gaps_are_named_by_the_open_span(reduced):
    # the gaps fall in the benchmark's own polling sleep, or outside any
    # span: both are the program's host loop at work
    labels = {label for label, _ in reduced["idle_gaps"]}
    assert labels == {trace_reduce.UNSPANNED}
    longest = [t for _, t in reduced["idle_gaps"][:3]]
    assert all(t == pytest.approx(0.0103, abs=0.0002) for t in longest)
    assert sum(reduced["idle_by_span"].values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"])


def test_short_op_names():
    assert trace_reduce.short_name("%while.93 = (s32[]) while(...)") == (
        "%while.93")
    assert trace_reduce.short_name("sort.0") == "sort.0"


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError, match="no device operation"):
        trace_reduce.reduce(TRACE)  # the TPU planes are not there


def test_unknown_device_kind_is_refused():
    peaks = {"source": "x", "TPU v5 lite": {"hbm_bytes_per_s": 8.19e11}}
    assert costs.peak(peaks, "TPU v5 lite", "hbm_bytes_per_s") == 8.19e11
    for kind in ("TPU v4", "cpu", "source"):
        with pytest.raises(costs.UnknownDevice):
            costs.peak(peaks, kind, "hbm_bytes_per_s")


def test_the_committed_peaks_table():
    import json

    with open(os.path.join(os.path.dirname(trace_reduce.__file__),
                           "peaks.json")) as f:
        peaks = json.load(f)
    assert costs.peak(peaks, "TPU v5 lite", "hbm_bytes_per_s") == 819e9
    assert "source" in peaks


def test_wave_bytes_lower_bound():
    # 10 rows of a 44-bit state (8 bytes), 30 candidates, 12 new states
    assert costs.row_bytes(44) == 8 and costs.row_bytes(616) == 80
    assert costs.wave_bytes(10, 30, 12, 44) == 10 * 8 + 30 * 8 + 12 * 28
