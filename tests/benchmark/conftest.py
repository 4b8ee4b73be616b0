"""Helpers for the benchmark's own tests: they drive the harness's
functions on the CPU at small sizes (the benchmark itself refuses to
run without a TPU), and describe no TPU topology."""

import json
import os
import time
import types

import pytest


@pytest.fixture
def harness():
    """``harness(config, seconds)`` runs the check-stream driver the way
    ``run.py`` does, on the CPU's first device."""
    import jax

    from benchmark import run

    def go(config, seconds=0.3, trace=0, out_dir=None):
        with open(os.path.join(run.BENCH, "traffic",
                               "check_stream.json")) as f:
            traffic = json.load(f)
        args = types.SimpleNamespace(workload="test", seed=7,
                                     seconds=seconds, trace=trace)
        ctx = {"config": config, "traffic": traffic, "args": args,
               "t0": time.monotonic(), "devices": jax.devices()[:1],
               "load_plugin": run.load_plugin,
               "peaks": run.load_json(os.path.join(run.BENCH,
                                                   "peaks.json")),
               "out_dir": out_dir or "/nonexistent"}
        res = run.load_plugin("drivers", "check_stream").run(ctx)
        return ctx, res

    return go
