"""``sym_fold_share`` and ``eventually_pending_share``: what the symmetry
representative and the eventually bits did over the window. Synthetic
entries pin the arithmetic; logs without the counters (a program built
without symmetry or an eventually property, or one that predates them)
read None."""

import json
import os

import pytest

from benchmark import run

DATA = os.path.join(os.path.dirname(__file__), "data")


def _read(name, entries, trace=True):
    ctx = {"window": {"entries": entries},
           "trace": {"idle_share": 0.0} if trace else None,
           "config": {}}
    return run.load_plugin("metrics", name).read(ctx)


def test_fold_share_is_folded_over_successors():
    entries = [{"successors": 1000, "sym_folded": 120},
               {"successors": 3000, "sym_folded": 380}]
    assert _read("sym_fold_share", entries) == 500 / 4000


def test_pending_share_is_pending_over_admitted():
    entries = [{"novel": 400, "eventually_pending": 400},
               {"novel": 600, "eventually_pending": 100}]
    assert _read("eventually_pending_share", entries) == 500 / 1000


@pytest.mark.parametrize("name,key,denominator", [
    ("sym_fold_share", "sym_folded", "successors"),
    ("eventually_pending_share", "eventually_pending", "novel")])
@pytest.mark.parametrize("entries", [
    [{"counter": None, "denominator": 10}],
    [{"denominator": 10}],
    [{"counter": 0, "denominator": 0}],
    []])
def test_none_without_the_counter(name, key, denominator, entries):
    renamed = [{(key if k == "counter" else denominator): v
                for k, v in e.items()} for e in entries]
    assert _read(name, renamed) is None


@pytest.mark.parametrize("name,key,denominator", [
    ("sym_fold_share", "sym_folded", "successors"),
    ("eventually_pending_share", "eventually_pending", "novel")])
def test_none_without_a_trace(name, key, denominator):
    assert _read(name, [{key: 1, denominator: 4}], trace=False) is None


@pytest.mark.parametrize("log", ["tpu_2pc4.json", "tpu_mesh_2pc5.json"])
@pytest.mark.parametrize("name", ["sym_fold_share",
                                  "eventually_pending_share"])
def test_recorded_logs_without_the_counters_read_none(log, name):
    with open(os.path.join(DATA, log)) as f:
        meta = json.load(f)
    assert meta["dispatch_log"]
    assert _read(name, meta["dispatch_log"]) is None
