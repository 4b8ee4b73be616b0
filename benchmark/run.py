"""The chip benchmark: one cell of ``BENCHMARK.json``, one process.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Everything is found by name. The cell (an entry of ``workloads`` in
``BENCHMARK.json``) names a configuration, whose file holds the model
and its engine sizes, and a traffic mix, ``benchmark/traffic/<name>.json``,
which names its driver, ``benchmark/drivers/<driver>.py``. Each per-layer
metric is read by ``benchmark/metrics/<metric>.py``. Adding a cell, a
configuration or a metric is adding files and entries; no file here
changes.

With ``--trace 0`` the result line carries the cell's end-to-end
metrics; with ``--trace 1`` its per-layer metrics, the device's busy
and window seconds, and a breakdown. The last line of standard output
is that JSON object; the numbers compared with the reference, each
beside its limit, are the last lines of standard error and the last
key of the line. There is no CPU mode: without a TPU, or with fewer
chips than the cell asks for, the run exits non-zero and prints no
result.
"""

import time

_T0 = time.monotonic()  # set-up is timed from here

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
#: JAX's persistent compilation cache, at a fixed path inside the
#: checkout: only a cell's first run in a checkout compiles.
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
#: traces and other run output (gitignored)
OUT_DIR = os.path.join(ROOT, ".bench_out")


class BenchError(Exception):
    """The benchmark cannot run here (no chip, unknown name, ...)."""


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_plugin(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` as a module."""
    path = os.path.join(BENCH, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise BenchError(f"no {kind} file for {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def resolve(spec: dict, workload: str, root: str = ROOT) -> dict:
    """The cell named ``workload`` with its configuration, traffic mix
    and metric lists, all found by name."""
    cells = {c["name"]: c for c in spec["workloads"]}
    if workload not in cells:
        raise BenchError(f"no cell {workload!r}; cells: {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    entry = configs[cell["config"]]
    config = load_json(os.path.join(root, entry["file"]))
    traffic = load_json(os.path.join(BENCH, "traffic",
                                     f"{cell['traffic']}.json"))
    end_to_end = [m for m in spec["end_to_end"]
                  if workload in m.get("workloads", [workload])]
    reported = {m["name"] for m in end_to_end}
    per_layer = [m for m in spec["per_layer"]
                 if workload in m.get("workloads", [workload])
                 and m["moves"] in reported]
    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": end_to_end, "per_layer": per_layer}


def chips(count: int):
    """The first ``count`` TPU devices; raises without them."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX found {devices[0].platform}")
    if len(devices) < count:
        raise BenchError(f"the cell asks for {count} chips, JAX sees "
                         f"{len(devices)}")
    return devices[:count]


def device_info(devices) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices),
            "memory_peak_bytes": peak}


def read_per_layer(metrics: list, ctx: dict) -> dict:
    """Each per-layer metric from its reader; a reader that finds
    nothing to read returns None and the metric is left out."""
    out = {}
    for m in metrics:
        value = load_plugin("metrics", m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def result_line(res: dict, metrics: dict, device: dict, trace: bool
                ) -> dict:
    """The last line: the driver's keys, then ``compared`` last."""
    line = {"correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"], "metrics": metrics, "device": device}
    if trace and res.get("breakdown"):
        line["breakdown"] = res["breakdown"]
    line["compared"] = res["compared"]
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        spec = load_json(os.path.join(ROOT, "BENCHMARK.json"))
        plan = resolve(spec, args.workload)
        sys.path.insert(0, ROOT)
        # libtpu logs to /tmp/tpu_logs unless told otherwise
        if "TPU_LOG_DIR" not in os.environ:
            os.environ["TPU_LOG_DIR"] = os.path.join(OUT_DIR, "tpu_logs")
            os.makedirs(os.environ["TPU_LOG_DIR"], exist_ok=True)
        import jax

        # Before the program's first compile; the program keeps a
        # directory set here (jit_cache.enable_persistent_jit_cache).
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
        devices = chips(plan["cell"]["chips"])
        driver = load_plugin("drivers", plan["traffic"]["driver"])
    except BenchError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    ctx = dict(plan, args=args, t0=_T0, devices=devices,
               load_plugin=load_plugin,
               peaks=load_json(os.path.join(BENCH, "peaks.json")),
               out_dir=os.path.join(OUT_DIR, args.workload))
    res = driver.run(ctx)
    device = dict(device_info(devices), **res.get("device", {}))
    if args.trace:
        metrics = read_per_layer(plan["per_layer"], dict(ctx, **res))
    else:
        metrics = {m["name"]: {"value": res["end_to_end"][m["name"]],
                               "unit": m["unit"]}
                   for m in plan["end_to_end"]}
    for name, c in res["compared"].items():
        print(f"compared {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(json.dumps(result_line(res, metrics, device, args.trace)),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
