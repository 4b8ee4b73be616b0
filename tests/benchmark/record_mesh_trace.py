"""Records the small four-chip TPU trace that ``test_exchange.py`` reads:
one sharded-fused check of 2pc with 5 resource managers on four chips,
under the profiler, inside a ``bench.traced`` span, with its dispatch
log. Run it on a host with four TPU chips from the root of the checkout:

    python tests/benchmark/record_mesh_trace.py [out_dir]

It writes ``tpu_mesh_2pc5.xplane.pb`` and ``tpu_mesh_2pc5.json`` to
``out_dir`` (default ``tests/benchmark/data``), cut as
``record_tpu_trace.py`` cuts its trace: the first chip's ops, the host
spans, and the scope paths of the ops that carry none.
"""

import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

from benchmark import trace_reduce  # noqa: E402
from record_tpu_trace import cut  # noqa: E402

NAME = "tpu_mesh_2pc5"
SHARDS = 4
#: per shard
SIZES = {"batch_size": 64, "table_capacity": 1 << 14,
         "arena_capacity": 1 << 13}
LOG_KEYS = ("waves", "rows", "bucket", "candidates", "novel",
            "probe_rounds", "dedup_rounds", "host_s", "exchange_rows",
            "exchange_slots")


def main(out_dir: str) -> None:
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from stateright_tpu.service.registry import default_registry

    model, _ = default_registry().build("twopc", {"rm_count": 5})
    mesh = Mesh(np.array(jax.devices()[:SHARDS]), ("shard",))

    def check():
        return model.checker().spawn_tpu_bfs(fused=True, mesh=mesh,
                                             **SIZES).join()

    check()  # compiles
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
        checker = check()
    jax.profiler.stop_trace()
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"{NAME}.xplane.pb"), "wb") as f:
        f.write(cut(trace_reduce.newest_xplane(tmp)))
    shutil.rmtree(tmp)
    log = [{k: e[k] for k in LOG_KEYS} for e in checker.dispatch_log]
    with open(os.path.join(out_dir, f"{NAME}.json"), "w") as f:
        json.dump({"sizes": SIZES, "shards": SHARDS, "row_bits": 24,
                   "unique": checker.unique_state_count(),
                   "device": jax.devices()[0].device_kind,
                   "dispatch_log": log}, f, indent=1)


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else os.path.join(HERE, "data"))
