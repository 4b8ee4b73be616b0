"""``chip_smoke.py`` and the bench's device stage, on the CPU.

The smoke has no CPU mode: here it must refuse. Its phase functions
are driven directly at small sizes (2pc-3, a 4-device virtual mesh),
which is what the chip run calls at full size. The bench runs its
device stage in its own process and refuses to run without a TPU
unless ``BENCH_PLATFORM=cpu`` asks for a rehearsal.
"""

import json
import os
import subprocess
import sys

import jax

import chip_smoke
from two_phase_commit import TwoPhaseSys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cpu_env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_PLATFORM", None)
    env.update(extra)
    return env


def test_smoke_refuses_the_cpu_in_process(capsys):
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out and "no TPU" in out.err


def test_smoke_script_refuses_the_cpu():
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=_REPO,
                          env=_cpu_env(), capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_check_phase_matches_native_engine():
    got = chip_smoke.phase_check(TwoPhaseSys(3), batch_size=64)
    assert got["unique"] == got["reference"]["unique"] == 288
    assert got["discoveries"] == got["reference"]["discoveries"]
    assert got["kernel_path"] == "xla"
    assert got["compile_sec"] >= 0 and got["check_sec"] >= 0


def test_service_phase_pins_three_jobs():
    got = chip_smoke.phase_service()
    assert got["jobs"] == [dict(chip_smoke.TWOPC_PIN, state="done")] * 3


def test_sharded_phase_on_four_virtual_devices():
    got = chip_smoke.phase_sharded(TwoPhaseSys(3),
                                   devices=jax.devices()[:4],
                                   batch_size=32)
    assert got["shards"] == 4
    assert sum(got["shard_occupancy"]) == got["unique"] == 288
    assert min(got["shard_occupancy"]) > 0


def test_bench_exits_nonzero_without_a_tpu():
    proc = subprocess.run([sys.executable, "bench.py"], cwd=_REPO,
                          env=_cpu_env(), capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["value"] == 0.0 and "no TPU" in result["error"]
    assert result["device"]["platform"] == "cpu"


def test_bench_cpu_rehearsal_runs_in_one_process():
    env = _cpu_env(BENCH_PLATFORM="cpu", BENCH_WORKLOAD="2pc",
                   BENCH_2PC_RMS="3", BENCH_PARITY_RMS="3",
                   BENCH_HOST_CAP="500", BENCH_TPU_CAP="2000",
                   BENCH_TPU_BATCH="64", BENCH_TPU_MAX_BATCH="64",
                   BENCH_BUDGET_S="50")
    proc = subprocess.run([sys.executable, "bench.py"], cwd=_REPO,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] >= 1
    assert result["platform"] == result["parity_backend"] == "cpu"
    assert "on cpu" in result["metric"] and result["value"] > 0
    assert "error" not in result, result["error"]
