"""Test configuration.

Forces JAX onto a virtual 8-device CPU mesh so multi-chip sharding code
paths compile and execute without TPU hardware, and so a test run never
reaches for a chip.

``jax.config.update("jax_platforms", ...)`` is used as well as the
environment variable, in case jax was imported before this file; it
works as long as no backend has been initialized. ``XLA_FLAGS`` is read
lazily at CPU-client creation, for the virtual device count.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
assert not jax._src.xla_bridge._backends, \
    "a JAX backend was initialized before conftest could force CPU"

_repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _repo)

# -- Tier-1 per-test runtime budget --------------------------------------
#
# The fast suite runs under a hard 870s timeout (ROADMAP tier-1) and
# round 9 left it at ~820s — one slow new test away from zeroing the
# whole verify. This guard makes the regression local and attributable:
# any test NOT marked `slow` that exceeds the per-test budget fails
# with instructions, instead of the suite silently creeping into the
# timeout. The budget is deliberately ~3x the slowest legitimate fast
# test (so a loaded box doesn't flake it); STpu_TEST_BUDGET_S
# overrides, 0 disables.

import time  # noqa: E402

import pytest  # noqa: E402

_TEST_BUDGET_S = float(os.environ.get("STpu_TEST_BUDGET_S", "75"))

#: the hard wall-clock timeout the tier-1 suite runs under (ROADMAP
#: tier-1: ``timeout -k 10 870``); the terminal summary warns loudly
#: when a run crosses 90% of it — the last attributable moment before
#: the whole verify starts zeroing on timeout.
_TIER1_WALL_BUDGET_S = 870.0

_SESSION_T0 = time.monotonic()

#: per-FILE accumulated test seconds (round 15): the 870s timeout is
#: consumed file by file, so the terminal summary prints the top-5
#: files — the margin (and which file to thin next) is visible in
#: every tier-1 log instead of needing a --durations rerun.
_FILE_SECONDS: dict = {}


@pytest.fixture(autouse=True)
def _tier1_per_test_budget(request):
    t0 = time.monotonic()
    yield
    dur = time.monotonic() - t0
    fname = os.path.basename(str(request.node.fspath))
    _FILE_SECONDS[fname] = _FILE_SECONDS.get(fname, 0.0) + dur
    if (_TEST_BUDGET_S > 0 and dur > _TEST_BUDGET_S
            and not request.node.get_closest_marker("slow")):
        pytest.fail(
            f"{request.node.nodeid} ran {dur:.1f}s, over the "
            f"{_TEST_BUDGET_S:.0f}s tier-1 per-test budget: mark it "
            "@pytest.mark.slow or split it (the fast suite runs under "
            "a hard 870s timeout; see ROADMAP tier-1)", pytrace=False)


def pytest_terminal_summary(terminalreporter):
    if not _FILE_SECONDS:
        return
    total = sum(_FILE_SECONDS.values())
    top = sorted(_FILE_SECONDS.items(), key=lambda kv: -kv[1])[:5]
    terminalreporter.write_line(
        f"tier-1 budget: {total:.0f}s of test time measured; "
        "slowest files:")
    for name, sec in top:
        terminalreporter.write_line(
            f"  {sec:7.1f}s  {name} ({100 * sec / max(total, 1e-9):.0f}%)")
    # Wall-clock projection against the tier-1 hard timeout (round 20):
    # wall includes collection/import overhead the per-test accumulator
    # misses, so it is the number the `timeout` wrapper actually kills.
    wall = time.monotonic() - _SESSION_T0
    frac = wall / _TIER1_WALL_BUDGET_S
    terminalreporter.write_line(
        f"tier-1 budget: {wall:.0f}s wall of the "
        f"{_TIER1_WALL_BUDGET_S:.0f}s hard timeout "
        f"({100 * frac:.0f}%)")
    if frac > 0.9:
        terminalreporter.write_line(
            f"*** TIER-1 BUDGET WARNING: {wall:.0f}s wall is over 90% "
            f"of the {_TIER1_WALL_BUDGET_S:.0f}s hard timeout — the "
            "fast suite is one slow test away from zeroing on timeout. "
            "Mark the heaviest tests in the files above "
            "@pytest.mark.slow or split them.", red=True, bold=True)


# The persistent jit cache is NOT enabled for tests. It used to be
# force-enabled on the CPU backend for the ~3x warm-run speedup, on the
# theory that the AOT loader's "could lead to execution errors such as
# SIGILL" warning was cosmetic. It is not cosmetic: cache-deserialized
# XLA:CPU executables mishandle DONATED buffers — runs stayed
# count-correct but the donated visited-table/arena chain read back
# with stale slots, zeros, and heap-pointer garbage (reproduced on the
# seed engine too; ~30-100% of runs once a cached donating dispatch
# program loads). The engines donate everywhere by design, so the
# cache must stay off here; see jit_cache.py.
