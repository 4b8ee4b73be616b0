"""check_mesh: one exhaustive check on a mesh of chips, from its initial
state, through the user's entry point.

The check is ``model.checker().spawn_tpu_bfs(fused=True, mesh=Mesh(
devices, ("shard",)), **sizes)`` over the cell's chips. The
configuration's ``spawn`` holds the whole mesh's sizes, and each shard
gets ``spawn / shards``: the engine's sizes are per shard. The window,
``setup_s`` and ``states_per_s`` are ``check_stream``'s (its
``Window``). So is the traced stretch (its ``_Tracer``), except where
it begins: ``trace_s`` before the window's nominal end (``_tail_tracer``).

After the window the check is stopped (``preempt``) and the rows every
shard admitted are read back (``arena_rows``). A mesh check takes each
wave's rows from every shard's own queue, so what it has done is not a
prefix of one breadth-first order; it is judged by what holds in any
order (``benchmark/reference/twopc_closure.py``):

- ``unique_diff``: admitted states that repeat, that are neither the
  initial state nor a child of their expanded parent, children of
  expanded states that were not admitted, and the distance of the
  reported unique count from the admitted rows;
- ``head_diff``: the expanded rows the dispatch log reports against the
  rows read back below the shards' heads;
- ``states_diff``: the reported generated states against 1 plus what
  the expanded states generate;
- ``disc_diff``: discovery paths that do not replay to a state where
  their property is met, and properties an expanded state meets that
  were not discovered (or discovered with no such state);
- ``errors``: 1 where the engine raised or its answers could not be
  read.

Every limit is 0.
"""

from __future__ import annotations

import traceback

import numpy as np

COMPARED = ("head_diff", "unique_diff", "states_diff", "disc_diff",
            "errors")


def reference_states(lanes: np.ndarray, n: int) -> np.ndarray:
    """``twopc.py``'s integer of each row of the program's 2pc lanes
    (``tpu/models/twopc.py``: RM states, TM state, prepared mask,
    message mask): RM ``i`` at bit ``2i``, the TM at ``2n``, the
    prepared mask at ``2n+2`` and the message mask (commit, abort,
    prepared(i)) at ``3n+2``."""
    lanes = np.asarray(lanes).astype(np.uint64)
    out = ((lanes[:, n] << np.uint64(2 * n))
           | (lanes[:, n + 1] << np.uint64(2 * n + 2))
           | (lanes[:, n + 2] << np.uint64(3 * n + 2)))
    for i in range(n):
        out |= lanes[:, i] << np.uint64(2 * i)
    return out


def read_check(checker, dm, n: int) -> dict:
    """What a finished check admitted, expanded, counted and discovered,
    in the reference's states."""
    from benchmark.reference.twopc_closure import NO_PARENT

    shards = checker.arena_rows()
    lanes = np.concatenate([s["lanes"] for s in shards])
    fps = np.concatenate([s["fps"] for s in shards])
    parent_fps = np.concatenate([s["parents"] for s in shards])
    admitted = reference_states(lanes, n)
    order = np.argsort(fps, kind="stable")
    at = np.minimum(np.searchsorted(fps[order], parent_fps), len(fps) - 1)
    found = fps[order][at] == parent_fps
    parents = np.where(found, admitted[order][at], NO_PARENT)
    expanded = np.concatenate([np.arange(len(s["fps"])) < s["head"]
                               for s in shards])
    paths = {name: reference_states(np.stack(
                 [dm.encode(s) for s in path.into_states()]), n).tolist()
             for name, path in checker.discoveries().items()}
    return {"admitted": admitted, "parents": parents,
            "expanded": expanded,
            "unique_count": checker.unique_state_count(),
            "state_count": checker.state_count(), "paths": paths,
            "logged_rows": sum(e["rows"] for e in checker.dispatch_log),
            "head_rows": sum(s["head"] for s in shards)}


def compare(check: dict, ref) -> dict:
    """The compared numbers of a read-back check."""
    out = ref.judge(**{k: check[k] for k in (
        "admitted", "parents", "expanded", "unique_count", "state_count",
        "paths")})
    out["head_diff"] = abs(check["logged_rows"] - check["head_rows"])
    return out


def judge(checker, dm, ref, join) -> dict:
    """Stops the check, reads back what it admitted, and returns the
    driver's verdict keys."""
    checker.preempt()
    worst = dict.fromkeys(COMPARED, 0)
    try:
        join(checker)
        worst.update(compare(read_check(checker, dm, ref.n), ref))
    except Exception:  # noqa: BLE001 — an answer that cannot be read
        traceback.print_exc()
        worst["errors"] = 1
    failed = int(any(worst.values()))
    return {"correct": failed == 0, "attempted": 1, "failed": failed,
            "compared": {k: {"value": v, "limit": 0}
                         for k, v in worst.items()}}


def _tail_tracer(base):
    """``check_stream``'s traced stretch, begun ``trace_s`` before the
    window's nominal end rather than before the next dispatch is due.
    A mesh dispatch of 2pc-11 takes about as long as the window (16
    waves of ~0.6 s), so the window holds one or two dispatch intervals
    and none can be measured before it closes; a stretch that ends at
    or after the window's closing dispatch still holds whole waves and,
    mostly, that dispatch boundary. It stops as ``base`` does."""

    class TailTracer(base):
        def __init__(self, out_dir: str, traffic: dict, seconds: float):
            super().__init__(out_dir, traffic)
            self.seconds = seconds

        def poll(self, elapsed: float, log: list):
            import jax

            if (self.t_start is None
                    and elapsed >= self.seconds - self.lead):
                jax.profiler.start_trace(self.dir)
                self.span = jax.profiler.TraceAnnotation("bench.traced")
                self.span.__enter__()
                self.t_start, self.n_start = elapsed, len(log)
            elif self.span is not None:
                super().poll(elapsed, log)

    return TailTracer


def run(ctx: dict) -> dict:
    """One run of a check-mesh cell (see the module docstring)."""
    import jax
    from jax.sharding import Mesh

    from stateright_tpu.tpu.fused import FusedTpuBfsChecker

    cs = ctx["load_plugin"]("drivers", "check_stream")
    if not hasattr(FusedTpuBfsChecker, "arena_rows"):
        raise cs.CheckFailed("the program gives no read of the rows a "
                             "check admitted (arena_rows): a mesh check "
                             "cannot be judged")
    args, config, traffic = ctx["args"], ctx["config"], ctx["traffic"]
    shards = int(config["shards"])
    if shards != len(ctx["devices"]):
        raise cs.CheckFailed(f"{config['name']} has {shards} shards, the "
                             f"cell {len(ctx['devices'])} chips")
    sizes = {k: v // shards for k, v in config["spawn"].items()}
    model, _ = cs.build_model(config)
    mesh = Mesh(np.array(ctx["devices"]), ("shard",))

    def spawn():
        return model.checker().spawn_tpu_bfs(fused=True, mesh=mesh,
                                             **sizes)

    tracer = (_tail_tracer(cs._Tracer)(ctx["out_dir"], traffic,
                                       args.seconds)
              if args.trace else None)
    win = cs.Window(spawn, args.seconds, traffic["poll_s"],
                    span=jax.profiler.TraceAnnotation)
    t_open = win.open()
    if tracer is not None:
        tracer.t_open = t_open
    setup_s = t_open - ctx["t0"]
    window_s = win.run(on_poll=tracer.poll if tracer else None)
    if tracer is not None:
        tracer.stop(win.checker.dispatch_log[:win.last + 1])
    memory_peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use",
                                                      0))
                      for d in ctx["devices"])
    entries = win.entries()
    ref_cfg = config["reference"]
    ref = ctx["load_plugin"]("reference", ref_cfg["module"]).make(
        ref_cfg["params"])
    verdict = judge(win.checker, model.device_model(), ref, cs._join)
    res = dict(verdict,
               end_to_end={"setup_s": setup_s,
                           "states_per_s": win.admitted / window_s},
               window={"entries": entries, "seconds": window_s,
                       "admitted": win.admitted},
               compile_s=win.compile_s,
               device={"memory_peak_bytes": memory_peak})
    red = tracer.reduce() if tracer is not None else None
    if red is not None:
        res["trace"] = red
        res["device"].update(busy_s=red["busy_s"],
                             window_s=red["window_s"])
        res["breakdown"] = {"device_ops": red["device_ops"],
                            "idle_gaps": red["idle_gaps"]}
    return res
