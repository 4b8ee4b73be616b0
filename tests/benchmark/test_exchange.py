"""The shard exchange's yardstick (``benchmark/exchange.py``) and its
readers: ``exchange_ms``, ``exchange_fill`` and ``exchange_ici_share``.

The arithmetic is pinned on numbers, the readers on a trace recorded on
four TPU chips (``data/tpu_mesh_2pc5.xplane.pb``, cut by
``record_mesh_trace.py``: one sharded-fused 2pc-5 check at batch 64 per
shard, with its dispatch log in ``data/tpu_mesh_2pc5.json``)."""

import json
import os
import types

import pytest

from benchmark import exchange, run, trace_stages

DATA = os.path.join(os.path.dirname(__file__), "data")
MESH_TRACE = os.path.join(DATA, "tpu_mesh_2pc5.xplane.pb")
METRICS = ("exchange_ms", "exchange_fill", "exchange_ici_share")


def test_least_bytes_of_a_row_and_a_wave():
    # a 48-bit 2pc-11 row packs into 2 words; beside it the dedup, path
    # and parent fingerprints and the property bits
    assert exchange.row_exchange_bytes(48) == 8 + 8 + 8 + 8 + 4
    assert exchange.row_exchange_bytes(24) == 4 + 28
    # 4000 rows left their shard over 10 waves of a 4-shard mesh
    assert exchange.shard_wave_bytes(4000, 4, 10, 48) == 100 * 36


def test_ici_share_is_bytes_over_time_over_the_peak():
    # 3.6 MB in 1 ms is 3.6 GB/s, of 1600 Gbit/s = 200 GB/s
    assert exchange.ici_share(3.6e6, 1.0, 1.6e12) == pytest.approx(0.018)


def test_in_scope_reads_a_path_component():
    assert exchange.in_scope("jit(local)/shard_map/while/body/exchange/x")
    assert not exchange.in_scope("jit(local)/while/body/exchanged/x")


def _ctx(tmp_path, entries, trace=None, **config):
    return {"window": {"entries": entries}, "trace": trace,
            "out_dir": str(tmp_path),
            "config": dict({"shards": 4, "row_bits": 24}, **config),
            "devices": [types.SimpleNamespace(device_kind="TPU v5 lite")],
            "peaks": run.load_json(os.path.join(run.BENCH,
                                                "peaks.json"))}


def test_fill_is_rows_over_slots(tmp_path):
    entries = [{"waves": 16, "exchange_rows": 30, "exchange_slots": 300},
               {"waves": 2, "exchange_rows": 10, "exchange_slots": 100}]
    read = run.load_plugin("metrics", "exchange_fill").read
    assert read(_ctx(tmp_path, entries)) == 40 / 400


def test_readers_return_none_without_the_program_s_marks(tmp_path):
    """A single-chip check, or a program that does not count its
    exchange, reports none of the three and raises nothing."""
    single = [{"waves": 16, "candidates": 10, "exchange_rows": None,
               "exchange_slots": None}]
    older = [{"waves": 16, "candidates": 10}]
    for entries in (single, older, []):
        for trace in (None, {"idle_share": 0.0}):
            ctx = _ctx(tmp_path, entries, trace)
            for m in METRICS:
                assert run.load_plugin("metrics", m).read(ctx) is None


@pytest.fixture(scope="module")
def mesh():
    with open(os.path.join(DATA, "tpu_mesh_2pc5.json")) as f:
        meta = json.load(f)
    return trace_stages.read(MESH_TRACE), exchange.read(MESH_TRACE), meta


def test_mesh_trace_edges_are_the_checks_waves(mesh):
    """One edge a wave, and one more: at these sizes the table slice
    (2^14) grows once, and its rehash runs the dedup loop too (the
    cell's sizes never grow)."""
    stages, _, meta = mesh
    assert len(stages["edges"]) == 1 + sum(e["waves"] for e in
                                           meta["dispatch_log"])


def test_mesh_trace_exchange_is_part_of_the_wave(mesh):
    stages, ms, _ = mesh
    assert 0 < ms < stages["wave_device_ms"]
    covered = sum(stages["stage_ms"].values())
    assert 0 < covered <= stages["wave_device_ms"]


def test_mesh_trace_all_to_alls_are_in_exchange():
    paths = trace_stages.op_paths(MESH_TRACE)
    a2a = [p for op, p in paths.items() if "all-to-all" in op]
    assert a2a and all(exchange.in_scope(p) for p in a2a)


def test_readers_on_the_mesh_trace(tmp_path, mesh):
    _, ms, meta = mesh
    trace_dir = tmp_path / "trace"
    trace_dir.mkdir()
    with open(MESH_TRACE, "rb") as f:
        (trace_dir / "t.xplane.pb").write_bytes(f.read())
    entries = [e for e in meta["dispatch_log"] if e["waves"]]
    ctx = _ctx(tmp_path, entries, {"idle_share": 0.0},
               shards=meta["shards"], row_bits=meta["row_bits"])
    got = {m: run.load_plugin("metrics", m).read(ctx) for m in METRICS}
    assert got["exchange_ms"] == ms
    # a shard sends at most B*F rows into (n-1)*B*F off-shard slots
    assert 0 < got["exchange_fill"] <= 1 / (meta["shards"] - 1)
    assert 0 < got["exchange_ici_share"] <= 1.0
