"""``store_fill``: the admitted rows over the rows the store's append
trips carried. Synthetic entries pin the arithmetic; the dispatch logs
recorded on a TPU before the store counted its slots read None; a fused
check's own log reads its admitted rows over its slots."""

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
    return run.load_plugin("metrics", "store_fill").read(ctx)


def test_fill_is_admitted_over_slots():
    entries = [{"waves": 16, "novel": 300, "append_slots": 4000},
               {"waves": 2, "novel": 100, "append_slots": 1000}]
    assert _read(entries) == 400 / 5000
    # on a mesh the admitted rows of every shard share the slowest one's
    assert _read(entries, shards=4) == 100 / 5000


@pytest.mark.parametrize("entries", [
    [{"waves": 16, "novel": 300, "append_slots": None}],
    [{"waves": 16, "novel": 300}],
    [{"waves": 0, "novel": 0, "append_slots": 0}],
    []])
def test_none_without_slots(entries):
    assert _read(entries) is None


def test_none_without_a_trace():
    assert _read([{"waves": 1, "novel": 3, "append_slots": 8}],
                 trace=False) is None


@pytest.mark.parametrize("name", ["tpu_2pc4.json", "tpu_mesh_2pc5.json"])
def test_recorded_logs_without_the_counter_read_none(name):
    with open(os.path.join(DATA, name)) as f:
        meta = json.load(f)
    assert meta["dispatch_log"]
    assert _read(meta["dispatch_log"], shards=meta.get("shards", 1)) is None


def test_a_fused_check_s_log(monkeypatch):
    """With chunks of 8 rows a wave of TwoPhaseSys(3) at batch 8 takes
    several trips, each but the last full."""
    from stateright_tpu.tpu import engine

    sys.path.insert(0, os.path.join(ROOT, "examples"))
    from two_phase_commit import TwoPhaseSys

    monkeypatch.setattr(engine, "APPEND_CHUNK", 8)
    c = TwoPhaseSys(3).checker().spawn_tpu_bfs(batch_size=8,
                                               fused=True).join()
    log = c.dispatch_log
    fill = _read(log)
    assert fill == (sum(e["novel"] for e in log)
                    / sum(e["append_slots"] for e in log))
    assert 0.5 < fill <= 1
