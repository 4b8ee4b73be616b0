"""Chip smoke: the fused checker's main path, end to end, on a TPU.

    python chip_smoke.py             # one chip: check + service phases
    python chip_smoke.py --chips 4   # the sharded-fused path on 4 chips

One process holds the chip and reaches it only through the entry points
a user calls:

- *check*: ``PaxosModelCfg(3, 3)`` checked exhaustively by
  ``spawn_tpu_bfs(fused=True)`` (about 1.19M unique states), compared
  with the compiled host engine (``spawn_native_bfs``, no JAX): equal
  unique counts and discovery names.
- *service*: ``serve_service`` on a local port, three concurrent
  ``twopc`` jobs through ``tools/service_client.py``, each pinned at
  1146 states / 288 unique.
- ``--chips 4``: only ``spawn_tpu_bfs(sharded=True, fused=True)`` over
  every device against the host engine, with each shard's occupancy.

Readings (compile seconds apart from check seconds, persistent-cache
hits) are printed as they come; they are smoke readings, not benchmark
metrics. The last line is ``{"ok": true, "device": {...}}`` and is only
printed when every phase passed on a TPU. There is no CPU mode: with no
TPU the script exits non-zero. Tests drive the phase functions directly.
"""

import argparse
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

_ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _ROOT)
sys.path.insert(0, os.path.join(_ROOT, "examples"))
sys.path.insert(0, os.path.join(_ROOT, "tools"))

# The repo's own modules load first: without the repo beside it the
# script dies here, before it prints anything.
import jax  # noqa: E402
import service_client as sc  # noqa: E402
from paxos import PaxosModelCfg  # noqa: E402

from stateright_tpu.explorer import serve_service  # noqa: E402

#: the 2pc-3 pin every service job must reproduce (BASELINE.md)
TWOPC_PIN = {"states": 1146, "unique": 288}

#: engine sizes for paxos check 3, pre-sized so the run never grows:
#: the table holds 1.19M states plus one wave at load factor 1/2, and
#: the arena holds every state plus one wave (fused.py's rest points).
PAXOS3_ONE_CHIP = {"batch_size": 4096, "table_capacity": 1 << 22,
                   "arena_capacity": 1 << 21}
#: per-shard sizes on four chips (table and arena are per shard)
PAXOS3_FOUR_CHIPS = {"batch_size": 2048, "table_capacity": 1 << 20}


def say(**reading) -> None:
    print(json.dumps(reading), flush=True)


def device_info() -> dict:
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


class CacheCounter:
    """Counts JAX persistent-cache hits and misses of this process."""

    def __init__(self):
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def paxos3():
    return PaxosModelCfg(3, 3).into_model()


def _reference(model) -> dict:
    ref = (model.checker().threads(os.cpu_count() or 1)
           .spawn_native_bfs(model.device_model()).join())
    return {"unique": ref.unique_state_count(),
            "discoveries": sorted(ref.discoveries())}


def _compare(got: dict, ref: dict) -> None:
    if (got["unique"], got["discoveries"]) != (ref["unique"],
                                               ref["discoveries"]):
        raise AssertionError(f"device {got} != native host engine {ref}")


def phase_check(model, **spawn_kwargs) -> dict:
    """Checks ``model`` to completion on the fused engine and compares
    it with the native host engine."""
    t0 = time.monotonic()
    checker = model.checker().spawn_tpu_bfs(fused=True, **spawn_kwargs)
    checker.join()
    wall = time.monotonic() - t0
    got = {"unique": checker.unique_state_count(),
           "states": checker.state_count(),
           "discoveries": sorted(checker.discoveries()),
           "kernel_path": checker.kernel_path(),
           "compile_sec": round(checker.compile_sec, 3),
           "check_sec": round(wall - checker.compile_sec, 3)}
    ref = _reference(model)
    got["reference"] = ref
    _compare(got, ref)
    return got


def phase_service(jobs: int = 3) -> dict:
    """Three concurrent ``twopc`` jobs through the HTTP job service."""
    service, server = serve_service(addresses=("127.0.0.1", 0),
                                    block=False, workers=2)
    try:
        base = f"http://127.0.0.1:{server.server_address[1]}"
        spec = {"model": "twopc", "params": {"rm_count": 3}}

        def run(_):
            job = sc.submit(base, spec)
            return sc.wait_for(base, job["id"], timeout=600.0)

        with ThreadPoolExecutor(jobs) as pool:
            done = list(pool.map(run, range(jobs)))
    finally:
        server.shutdown()
        server.server_close()
        service.close()
    counts = [{"state": d["state"], "states": d.get("states"),
               "unique": d.get("unique")} for d in done]
    want = dict(TWOPC_PIN, state="done")
    if any(c != want for c in counts):
        raise AssertionError(f"service jobs {counts} != {want} each")
    return {"jobs": counts}


def phase_sharded(model, devices=None, **spawn_kwargs) -> dict:
    """The sharded-fused engine over ``devices`` (default: every visible
    device, as ``sharded=True`` takes them), compared with the native
    host engine; every shard must hold states."""
    import numpy as np
    from jax.sharding import Mesh

    devices = jax.devices() if devices is None else list(devices)
    mesh = Mesh(np.array(devices), ("shard",))
    t0 = time.monotonic()
    checker = model.checker().spawn_tpu_bfs(mesh=mesh, fused=True,
                                            **spawn_kwargs)
    checker.join()
    wall = time.monotonic() - t0
    occupancy = checker.shard_occupancy()
    got = {"unique": checker.unique_state_count(),
           "discoveries": sorted(checker.discoveries()),
           "shards": len(occupancy), "shard_occupancy": occupancy,
           "compile_sec": round(checker.compile_sec, 3),
           "check_sec": round(wall - checker.compile_sec, 3)}
    ref = _reference(model)
    got["reference"] = ref
    _compare(got, ref)
    if len(occupancy) != len(devices) or min(occupancy) <= 0:
        raise AssertionError(f"states did not spread over every device: "
                             f"{occupancy}")
    return got


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    info = device_info()
    if info["platform"] != "tpu":
        print(f"chip_smoke: no TPU (JAX found {info['platform']}); "
              "this script only runs on the chip", file=sys.stderr)
        return 2
    if info["count"] != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{info['count']} device(s)", file=sys.stderr)
        return 2
    say(phase="device", **info)
    cache = CacheCounter()
    if args.chips == 1:
        say(phase="check", model="paxos check 3",
            **phase_check(paxos3(), **PAXOS3_ONE_CHIP))
        say(phase="service", **phase_service())
    else:
        say(phase="sharded", model="paxos check 3",
            **phase_sharded(paxos3(), **PAXOS3_FOUR_CHIPS))
    say(phase="compile_cache", dir=jax.config.jax_compilation_cache_dir,
        hits=cache.hits, misses=cache.misses)
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
