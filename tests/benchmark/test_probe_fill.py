"""``probe_fill``: the candidates over the rows the probe's rounds
carried. Synthetic entries pin the arithmetic; the dispatch logs
recorded on a TPU before the probe counted its slots read None; a fused
check's own log reads its candidates over its slots."""

import json
import os
import sys

import pytest

from benchmark import run

DATA = os.path.join(os.path.dirname(__file__), "data")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _read(entries, trace=True, **config):
    ctx = {"window": {"entries": entries},
           "trace": {"idle_share": 0.0} if trace else None,
           "config": config}
    return run.load_plugin("metrics", "probe_fill").read(ctx)


def test_fill_is_candidates_over_slots():
    entries = [{"waves": 16, "candidates": 300, "probe_slots": 4000},
               {"waves": 2, "candidates": 100, "probe_slots": 1000}]
    assert _read(entries) == 400 / 5000
    # on a mesh the candidates of every shard share the slowest one's
    assert _read(entries, shards=4) == 100 / 5000


@pytest.mark.parametrize("entries", [
    [{"waves": 16, "candidates": 300, "probe_slots": None}],
    [{"waves": 16, "candidates": 300}],
    [{"waves": 0, "candidates": 0, "probe_slots": 0}],
    []])
def test_none_without_slots(entries):
    assert _read(entries) is None


def test_none_without_a_trace():
    assert _read([{"waves": 1, "candidates": 3, "probe_slots": 8}],
                 trace=False) is None


@pytest.mark.parametrize("name", ["tpu_2pc4.json", "tpu_mesh_2pc5.json"])
def test_recorded_logs_without_the_counter_read_none(name):
    with open(os.path.join(DATA, name)) as f:
        meta = json.load(f)
    assert meta["dispatch_log"]
    assert _read(meta["dispatch_log"], shards=meta.get("shards", 1)) is None


def test_a_fused_check_s_log():
    sys.path.insert(0, os.path.join(ROOT, "examples"))
    from two_phase_commit import TwoPhaseSys

    c = TwoPhaseSys(3).checker().spawn_tpu_bfs(batch_size=8,
                                               fused=True).join()
    log = c.dispatch_log
    fill = _read(log)
    assert fill == (sum(e["candidates"] for e in log)
                    / sum(e["probe_slots"] for e in log))
    assert 0 < fill <= 1
