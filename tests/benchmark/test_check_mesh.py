"""The mesh cell's driver and its order-free judge
(``benchmark/drivers/check_mesh.py``, ``benchmark/reference/
twopc_closure.py``) on four of the CPU's virtual devices, at small
sizes: a right check reads 0 in every compared number, after a short
window and after a whole check, and planted faults do not."""

import json
import os
import sys
import time
import types

import numpy as np
import pytest

import bench_control
from benchmark import run
from benchmark.drivers import check_mesh
from benchmark.reference import twopc, twopc_closure
from bench_helpers import ROOT

sys.path.insert(0, os.path.join(ROOT, "examples"))

SHARDS = 4


def _mesh_config(rm_count=5, batch=8):
    """A 2pc configuration at a CPU size, in the mesh configuration
    file's shape: ``spawn`` holds the whole mesh's sizes."""
    per_shard = {"batch_size": batch, "table_capacity": 1 << 13,
                 "arena_capacity": 1 << 12}
    return {"name": f"2pc-{rm_count}", "model": "twopc",
            "params": {"rm_count": rm_count}, "shards": SHARDS,
            "spawn": {k: SHARDS * v for k, v in per_shard.items()},
            "row_bits": 4 * rm_count + 4,
            "reference": {"module": "twopc_closure",
                          "params": {"rm_count": rm_count}}}


def _mesh():
    import jax
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:SHARDS]), ("shard",))


@pytest.mark.parametrize("n", [3, 4, 5, 6, 7])
def test_the_closure_counts_the_whole_space(n):
    got = twopc_closure.full_space(n)
    assert got["unique"] == 2**n * (3**n + 2**n + 1)
    want = twopc.TwoPhaseReference(n).complete()
    assert got == {"unique": want["unique"], "states": want["states"]}


def test_the_driver_runs_a_window_on_a_cpu_mesh():
    import jax

    with open(os.path.join(run.BENCH, "traffic", "check_mesh.json")) as f:
        traffic = json.load(f)
    args = types.SimpleNamespace(workload="test", seed=3000000019,
                                 seconds=0.2, trace=0)
    ctx = {"config": _mesh_config(), "traffic": traffic, "args": args,
           "t0": time.monotonic(), "devices": jax.devices()[:SHARDS],
           "load_plugin": run.load_plugin,
           "peaks": run.load_json(os.path.join(run.BENCH, "peaks.json")),
           "out_dir": "/nonexistent"}
    res = check_mesh.run(ctx)
    assert res["correct"], res["compared"]
    assert set(res["compared"]) == set(check_mesh.COMPARED)
    assert all(c == {"value": 0, "limit": 0}
               for c in res["compared"].values())
    assert res["end_to_end"]["states_per_s"] > 0
    assert all(e["exchange_rows"] <= e["exchange_slots"]
               for e in res["window"]["entries"])


def test_the_traced_stretch_ends_the_window(monkeypatch, tmp_path):
    """A mesh dispatch lasts about as long as the window, so the
    stretch begins ``trace_s`` before the window's nominal end and runs
    ``trace_tail_s`` past the next dispatch (or ``trace_max_s``)."""
    import jax

    from benchmark.drivers import check_stream

    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: calls.append("start"))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append("stop"))
    tracer = check_mesh._tail_tracer(check_stream._Tracer)(
        str(tmp_path), {"trace_s": 2.0, "trace_tail_s": 0.7,
                        "trace_max_s": 3.0}, 10.0)
    tracer.t_open = 100.0
    log = [{"t": 100.0}]
    tracer.poll(7.9, log)             # one dispatch: none due yet
    assert calls == []
    tracer.poll(8.0, log)             # trace_s before the nominal end
    assert calls == ["start"]
    log.append({"t": 109.9})          # the next dispatch, at 9.9 s
    tracer.poll(10.5, log)
    assert calls == ["start"]
    tracer.poll(10.7, log)            # trace_tail_s after it
    assert calls == ["start", "stop"] and tracer.dispatches == 1
    tracer.poll(12.0, log + [{"t": 119.8}])  # one stretch per run
    assert calls == ["start", "stop"]


@pytest.fixture(scope="module")
def checks():
    """A 2pc-5 check stopped after its second dispatch, and a whole
    one, each read back with the reference that judges it."""
    from two_phase_commit import TwoPhaseSys

    model = TwoPhaseSys(5)
    ref = twopc_closure.make({"rm_count": 5})
    sizes = {k: v // SHARDS for k, v in _mesh_config()["spawn"].items()}
    out = {}
    for kind in ("window", "whole"):
        c = model.checker().spawn_tpu_bfs(fused=True, mesh=_mesh(),
                                          **sizes)
        if kind == "whole":
            c.join()
        while len(c.dispatch_log) < 2:
            time.sleep(0.01)
        verdict = check_mesh.judge(c, model.device_model(), ref,
                                   lambda ch: ch.join())
        out[kind] = (verdict, check_mesh.read_check(
            c, model.device_model(), 5))
    return ref, out


@pytest.mark.parametrize("kind", ["window", "whole"])
def test_a_right_check_reads_zero(checks, kind):
    _, out = checks
    verdict, check = out[kind]
    assert verdict["correct"], verdict["compared"]
    expanded = int(check["expanded"].sum())
    if kind == "window":
        assert expanded < len(check["admitted"])
    else:
        assert len(check["admitted"]) == 8832
        assert expanded == 8832
        # "abort agreement" and "commit agreement" are met and found
        assert set(check["paths"]) == {"abort agreement",
                                       "commit agreement"}


@pytest.mark.parametrize("which", ["expanded", "frontier"])
def test_a_dropped_admitted_row_is_seen(checks, which):
    ref, out = checks
    check = dict(out["window"][1])
    rows = np.flatnonzero(check["expanded"] == (which == "expanded"))
    drop = rows[len(rows) // 2]
    keep = np.arange(len(check["admitted"])) != drop
    for key in ("admitted", "parents", "expanded"):
        check[key] = check[key][keep]
    got = check_mesh.compare(check, ref)
    assert got["unique_diff"] > 0
    if which == "expanded":
        # the dropped row's generated states are still counted
        assert got["states_diff"] > 0


def _lossy_check(n, bits, rows, seed=4000000007):
    """What a check that tells states apart by a ``bits``-bit salted
    fingerprint admits (``bench_control``'s lossy key), after expanding
    ``rows`` rows of its queue."""
    key = bench_control.lossy_key("twopc", seed, bits)
    search = twopc.TwoPhaseReference(n, dedup_key=key)
    search.ensure_expanded(rows)
    admitted = np.concatenate(search.levels)
    expanded = np.arange(len(admitted)) < search.expanded_rows()
    lay = twopc.Layout(n)
    kids, valid = twopc.children(lay, admitted[expanded])
    parent_of = {}
    for par, ks, vs in zip(admitted[expanded], kids, valid):
        for k in ks[vs]:
            parent_of.setdefault(int(k), par)
    parents = np.array([parent_of.get(int(s), twopc_closure.NO_PARENT)
                        for s in admitted], np.uint64)
    parents[0] = twopc_closure.NO_PARENT
    generated = int(valid.sum())
    return {"admitted": admitted, "parents": parents,
            "expanded": expanded, "unique_count": len(admitted),
            "state_count": 1 + generated, "paths": {},
            "logged_rows": int(expanded.sum()),
            "head_rows": int(expanded.sum())}


def test_a_lossy_dedup_key_is_seen():
    ref = twopc_closure.make({"rm_count": 5})
    got = check_mesh.compare(_lossy_check(5, bits=12, rows=3000), ref)
    assert got["unique_diff"] > 0
    assert got["states_diff"] == got["head_diff"] == 0
    exact = check_mesh.compare(_lossy_check(5, bits=64, rows=3000), ref)
    assert exact["unique_diff"] == 0


def test_program_lanes_map_onto_reference_states():
    """The driver's lane mapping sends the program's encoding of each
    successor of a state to a successor of the state's reference
    integer, and the initial state to the reference's."""
    from two_phase_commit import TwoPhaseSys

    model = TwoPhaseSys(4)
    dm = model.device_model()
    init = model.init_states()[0]
    lanes = np.stack([dm.encode(init)])
    assert check_mesh.reference_states(lanes, 4)[0] == twopc_closure.INIT
    lay = twopc.Layout(4)
    frontier = [init]
    for _ in range(4):
        nxt = []
        for s in frontier:
            mine = check_mesh.reference_states(np.stack([dm.encode(s)]), 4)
            kids, valid = twopc.children(lay, mine)
            succ = [t for _, t in model.next_steps(s)]
            got = check_mesh.reference_states(
                np.stack([dm.encode(t) for t in succ]), 4)
            assert sorted(got.tolist()) == sorted(kids[valid].tolist())
            nxt += succ
        frontier = nxt[:8]
