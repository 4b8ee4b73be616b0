"""The control of the check cells' comparison: the plain reference put
in the program's place, with the one guarantee the configurations
state (an exhaustive search that tells every state apart) broken the
way a later PR would be tempted to break it: new states are told
apart by a 32-bit fingerprint, salted from the seed, instead of by the
state. Colliding states are lost, and ``correct`` must come out false.

``LossyChecker`` looks to the harness like a check of the program: it
runs waves of the configured batch in a thread and appends a record
per dispatch of ``waves_per_dispatch`` waves to ``dispatch_log``.

    python3 tests/benchmark/bench_control.py <cell> <seconds> <seed>...

runs the harness's window and comparison on the control at the cell's
own sizes (on the machine with the chip, as the other readings are)
and prints one JSON line per seed.
"""

from __future__ import annotations

import hashlib
import json
import os
import sys
import threading
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

#: waves per record, as the fused engine's default dispatch
WAVES_PER_DISPATCH = 16


def lossy_key(module: str, seed: int, bits: int = 32):
    """A ``bits``-bit fingerprint of a reference state, salted by
    ``seed``."""
    salt = (seed * 0x9E3779B97F4A7C15 | 1) & (1 << 64) - 1
    if module == "twopc":
        mult = np.uint64(salt)

        def key(states):
            with np.errstate(over="ignore"):
                return (states * mult) >> np.uint64(64 - bits)
        return key
    salt_bytes = salt.to_bytes(8, "little")

    def key(state):
        digest = hashlib.blake2b(repr(_canonical(state)).encode(),
                                 digest_size=8, key=salt_bytes).digest()
        return int.from_bytes(digest, "little") >> (64 - bits)
    return key


def _canonical(x):
    """``x`` with every frozenset in a fixed order, so that its
    ``repr`` is the same in every process (Python salts the hash of a
    string per process, and a set's order follows it)."""
    if isinstance(x, frozenset):
        return tuple(sorted((_canonical(e) for e in x), key=repr))
    if isinstance(x, tuple):
        return tuple(_canonical(e) for e in x)
    return x


class _Path:
    def __init__(self, depth):
        self.depth = depth

    def into_states(self):
        return [None] * (self.depth + 1)


class LossyChecker:
    """A check of ``ref`` in waves of ``batch`` rows, in a thread."""

    def __init__(self, ref, batch: int, pause_s: float = 0.0):
        self.ref, self.batch, self.pause_s = ref, batch, pause_s
        self.dispatch_log = []
        self.compile_sec = 0.0
        self._stop = threading.Event()
        self._done = threading.Event()
        self._last = {"head": 0, "unique": 1, "states": 1,
                      "discoveries": {}}
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self):
        waves = 0
        try:
            while not self._stop.is_set():
                waves += WAVES_PER_DISPATCH
                r = self.ref.waves(self.batch, waves)
                prev = self._last
                self.dispatch_log.append({
                    "t": time.monotonic(), "waves": WAVES_PER_DISPATCH,
                    "rows": r["head"] - prev["head"],
                    "unique": r["unique"], "states": r["states"],
                    "bucket": self.batch, "novel":
                    r["unique"] - prev["unique"],
                    "successors": r["states"] - prev["states"],
                    "candidates": r["states"] - prev["states"]})
                self._last = r
                if r["head"] == r["unique"]:
                    break  # the queue drained
                time.sleep(self.pause_s)
        finally:
            self._done.set()

    def is_done(self):
        return self._done.is_set()

    def preempt(self):
        self._stop.set()

    def join(self):
        self._thread.join()
        return self

    def unique_state_count(self):
        return self._last["unique"]

    def state_count(self):
        return self._last["states"]

    def discoveries(self):
        return {n: _Path(d) for n, d in self._last["discoveries"].items()}


def run_control(config: dict, seconds: float, seed: int,
                bits: int = 32) -> dict:
    """The harness's window and comparison with the control in the
    program's place; returns the verdict keys."""
    from benchmark.drivers import check_stream as cs

    ref_cfg = config["reference"]
    module = _reference_module(ref_cfg["module"])
    batch = config["spawn"]["batch_size"]

    def spawn():
        return LossyChecker(module.make(
            ref_cfg["params"],
            dedup_key=lossy_key(ref_cfg["module"], seed, bits)), batch)

    win = cs.Window(spawn, seconds)
    win.open()
    win.run()
    return cs.judge(win, config,
                    lambda: module.make(ref_cfg["params"]))


def _reference_module(name: str):
    import importlib

    return importlib.import_module(f"benchmark.reference.{name}")


def main(argv) -> int:
    from benchmark import run

    cell, seconds, seeds = argv[1], float(argv[2]), argv[3:]
    plan = run.resolve(run.load_json(os.path.join(ROOT, "BENCHMARK.json")),
                       cell)
    for seed in seeds:
        t0 = time.monotonic()
        v = run_control(plan["config"], seconds, int(seed))
        print(json.dumps({"cell": cell, "seed": int(seed),
                          "correct": v["correct"],
                          "compared": {k: c["value"] for k, c
                                       in v["compared"].items()},
                          "seconds": time.monotonic() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
