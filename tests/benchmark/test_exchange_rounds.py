"""``exchange_rounds``: the shard exchange's rounds per wave. Synthetic
entries pin the arithmetic; the dispatch logs recorded on a TPU before
the exchange counted its rounds read None; a mesh check's own log reads
its rounds over its waves."""

import json
import os

import pytest

from benchmark import run

DATA = os.path.join(os.path.dirname(__file__), "data")


def _read(entries, trace=True):
    ctx = {"window": {"entries": entries},
           "trace": {"idle_share": 0.0} if trace else None}
    return run.load_plugin("metrics", "exchange_rounds").read(ctx)


def test_rounds_over_waves():
    entries = [{"waves": 16, "exchange_rounds": 16},
               {"waves": 2, "exchange_rounds": 5}]
    assert _read(entries) == 21 / 18


@pytest.mark.parametrize("entries", [
    [{"waves": 16, "exchange_rounds": None}],
    [{"waves": 16}],
    [{"waves": 0, "exchange_rounds": 0}],
    []])
def test_none_without_rounds(entries):
    assert _read(entries) is None


def test_none_without_a_trace():
    assert _read([{"waves": 1, "exchange_rounds": 1}], trace=False) is None


@pytest.mark.parametrize("name", ["tpu_2pc4.json", "tpu_mesh_2pc5.json"])
def test_recorded_logs_without_the_counter_read_none(name):
    with open(os.path.join(DATA, name)) as f:
        meta = json.load(f)
    assert meta["dispatch_log"]
    assert _read(meta["dispatch_log"]) is None


def test_a_mesh_check_s_log():
    import sys

    import jax
    import numpy as np
    from jax.sharding import Mesh

    sys.path.insert(0, os.path.join(run.ROOT, "examples"))
    from two_phase_commit import TwoPhaseSys

    c = TwoPhaseSys(3).checker().spawn_tpu_bfs(
        fused=True, batch_size=8,
        mesh=Mesh(np.array(jax.devices()[:4]), ("shard",))).join()
    log = c.dispatch_log
    rounds = _read(log)
    assert rounds == (sum(e["exchange_rounds"] for e in log)
                      / sum(e["waves"] for e in log))
    assert 1 <= rounds <= 4
