"""The comparison that decides ``correct`` is shown to fail: by the
control (the plain reference in the program's place, telling states
apart by a short salted fingerprint), and by faults planted in the
program underneath a whole harness run (CPU, small sizes). The cells
run on one chip, so the fault "the exchange between chips left out"
does not apply to them."""

import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

import bench_control
from bench_helpers import ROOT, small_config


@pytest.mark.parametrize("module,params,batch,bits", [
    ("twopc", {"rm_count": 6}, 64, 16),
    ("paxos", {"client_count": 2, "server_count": 3}, 64, 14),
])
def test_the_control_comes_out_not_correct(module, params, batch, bits):
    config = {"spawn": {"batch_size": batch},
              "reference": {"module": module, "params": params}}
    verdict = bench_control.run_control(config, 0.5, seed=4000000007,
                                        bits=bits)
    assert not verdict["correct"]
    assert verdict["compared"]["unique_diff"]["value"] > 0


def test_the_control_with_an_exact_key_is_correct():
    # 2pc states fit 64 bits, and an odd multiplier is a bijection:
    # the same harness then finds nothing wrong.
    config = {"spawn": {"batch_size": 64},
              "reference": {"module": "twopc", "params": {"rm_count": 6}}}
    verdict = bench_control.run_control(config, 0.5, seed=5, bits=64)
    assert verdict["correct"]


def test_the_paxos_control_key_repeats_by_seed():
    """The control's reading is reproducible from its seed: the key of
    a state does not follow Python's per-process string hashing."""
    code = ("import sys; sys.path.insert(0, 'tests/benchmark'); "
            "import bench_control as b; "
            "from benchmark.reference import paxos; "
            "r = paxos.make({'client_count': 2, 'server_count': 3}); "
            "s = r.init(); print([b.lossy_key('paxos', seed)(s) "
            "for seed in (7, 8)])")
    outs = {subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, check=True, text=True,
        capture_output=True,
        env=dict(os.environ, PYTHONHASHSEED=str(h))).stdout
        for h in (1, 2)}
    assert len(outs) == 1
    a, b = eval(outs.pop())
    assert a != b and 0 <= a < 1 << 32


def _unchanged(monkeypatch):
    """A step that returns its state unchanged."""
    from stateright_tpu.tpu.models.twopc import TwoPhaseDevice

    step = TwoPhaseDevice.step

    def same(self, vec):
        succs, valid = step(self, vec)
        return jnp.broadcast_to(vec, succs.shape), valid

    monkeypatch.setattr(TwoPhaseDevice, "step", same)


def _half_batch(monkeypatch):
    """Half of each wave's rows left out of the expansion."""
    from stateright_tpu.tpu import fused

    expand = fused.expand_frontier

    def half(dm, vecs, valid):
        keep = jnp.arange(valid.shape[0]) % 2 == 0
        return expand(dm, vecs, valid & keep)

    monkeypatch.setattr(fused, "expand_frontier", half)


def _altered(monkeypatch):
    """One successor per wave altered where it is produced."""
    from stateright_tpu.tpu import fused

    expand = fused.expand_frontier

    def altered(dm, vecs, valid):
        succ, sv, count, terminal = expand(dm, vecs, valid)
        i = jnp.argmax(sv)
        succ = succ.at[i, 0].set(succ[i, 0] ^ jnp.uint32(1))
        return succ, sv, count, terminal

    monkeypatch.setattr(fused, "expand_frontier", altered)


@pytest.mark.parametrize("fault", [_unchanged, _half_batch, _altered],
                         ids=["state_unchanged", "half_batch",
                              "answer_altered"])
def test_a_fault_in_the_timed_path_is_not_correct(harness, monkeypatch,
                                                  fault):
    fault(monkeypatch)
    _ctx, res = harness(small_config(4, batch=16), seconds=0.3)
    assert not res["correct"]
    assert res["failed"] >= 1
    assert any(c["value"] > c["limit"] for c in res["compared"].values())

