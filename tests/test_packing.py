"""The bit-packed state arena (tpu/packing.py + ISSUE 4 acceptance).

Four contracts:

- **Layout compiler**: pack∘unpack == id on random in-range rows
  (plain and sentinel lanes, word-straddling fields, numpy and jitted
  codecs agree); invalid specs are rejected at BUILD time.
- **Bit-identical parity matrix**: counts, discoveries, and parent maps
  identical with ``pack_arena`` on vs off, on all four device engines,
  on 2pc and paxos — the sharded pair on the 8-device virtual mesh
  (covering the packed all-to-all exchange).
- **Cross-version checkpoint matrix**: v1-style unpacked snapshots
  resume on packed engines and vice versa (including the native C++
  reader), byte-for-byte identical continuation counts.
- **Telemetry**: wave events carry the v2 bandwidth gauges,
  ``scheduler_stats()["packing"]`` reports the real widths, and the
  north-star model actually achieves the >= 2.5x row-byte cut.
"""

import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "examples"))

from two_phase_commit import TwoPhaseSys  # noqa: E402

from stateright_tpu.tpu.packing import compile_layout  # noqa: E402


def _spawn(model, engine, B, **kwargs):
    b = model.checker()
    if engine == "fused":
        return b.spawn_tpu_bfs(batch_size=B, fused=True, **kwargs)
    if engine == "classic":
        return b.spawn_tpu_bfs(batch_size=B, fused=False, **kwargs)
    if engine == "sharded-fused":
        return b.spawn_tpu_bfs(batch_size=B, sharded=True, **kwargs)
    assert engine == "sharded-classic"
    return b.spawn_tpu_bfs(batch_size=B, sharded=True, fused=False,
                           **kwargs)


ENGINES = ("fused", "classic", "sharded-fused", "sharded-classic")


# -- Layout compiler -------------------------------------------------------

def _random_rows(layout, rng, n=257):
    """Random in-range rows for a layout (sentinel lanes mix real
    values and the sentinel)."""
    cols = []
    for l in layout.lanes:
        if l.sentinel is None:
            hi = (1 << l.bits) if l.bits < 32 else (1 << 32)
            cols.append(rng.integers(0, hi, n, dtype=np.uint64))
        else:
            vals = rng.integers(0, (1 << l.bits) - 1, n, dtype=np.uint64)
            sent = rng.random(n) < 0.3
            cols.append(np.where(sent, np.uint64(l.sentinel), vals))
    return np.stack(cols, axis=1).astype(np.uint32)


def test_pack_unpack_roundtrip_random_layouts():
    rng = np.random.default_rng(9)
    for trial in range(25):
        w = int(rng.integers(1, 60))
        specs = []
        for _ in range(w):
            bits = int(rng.integers(1, 33))
            if bits < 32 and rng.random() < 0.25:
                specs.append((bits, 0xFFFFFFFF))
            else:
                specs.append(bits)
        layout = compile_layout(specs, w)
        rows = _random_rows(layout, rng)
        packed = layout.pack_np(rows)
        assert packed.shape == (len(rows), layout.packed_width)
        assert (layout.unpack_np(packed) == rows).all(), (trial, specs)
        layout.check_fits(rows)  # in-range rows must pass the guard
        # Single-lane extraction agrees with the full unpack.
        lane = int(rng.integers(0, w))
        assert (layout.lane_np(packed, lane) == rows[:, lane]).all()


def test_pack_unpack_jit_matches_numpy():
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(3)
    specs = [3, (7, 0xFFFFFFFF), 32, 17, (30, 0xFFFFFFFF), 1, 13, 29]
    layout = compile_layout(specs, len(specs))
    rows = _random_rows(layout, rng, n=64)
    packed_np = layout.pack_np(rows)
    packed_j = np.asarray(jax.jit(layout.pack)(jnp.asarray(rows)))
    assert (packed_j == packed_np).all()
    back = np.asarray(jax.jit(layout.unpack)(jnp.asarray(packed_np)))
    assert (back == rows).all()
    lane = np.asarray(jax.jit(
        lambda p: layout.lane(p, 1))(jnp.asarray(packed_np)))
    assert (lane == rows[:, 1]).all()


def test_layout_rejects_invalid_specs_at_build_time():
    with pytest.raises(ValueError, match="outside 1..32"):
        compile_layout([0, 4], 2)
    with pytest.raises(ValueError, match="outside 1..32"):
        compile_layout([33], 1)
    with pytest.raises(ValueError, match="state_width"):
        compile_layout([4, 4, 4], 2)
    with pytest.raises(ValueError, match="sentinel"):
        # sentinel inside the value range would be ambiguous
        compile_layout([(8, 100)], 1)
    with pytest.raises(ValueError, match="bits.*or"):
        compile_layout([(8, 1, 2)], 1)


def test_check_fits_catches_wrong_declaration():
    layout = compile_layout([2, 4], 2)
    layout.check_fits(np.array([[3, 15]], np.uint32))
    with pytest.raises(ValueError, match="lane 0"):
        layout.check_fits(np.array([[4, 15]], np.uint32))


def test_identity_layout_for_conservative_default():
    layout = compile_layout(None, 5)
    assert not layout.packs
    assert layout.packed_width == 5


def test_model_layouts_roundtrip_reachable_states():
    """Every packing-declaring model family: encode real reachable
    states and prove the declared widths hold them (the lane_bits
    contract, checked against the actual host enumeration)."""
    from increment import IncrementModel
    from linearizable_register import AbdModelCfg
    from paxos import PaxosModelCfg
    from single_copy_register import SingleCopyModelCfg

    for model in (TwoPhaseSys(4), IncrementModel(3),
                  PaxosModelCfg(1, 3).into_model(),
                  AbdModelCfg(2, 2).into_model(),
                  SingleCopyModelCfg(2, 1).into_model()):
        dm = model.device_model()
        layout = compile_layout(dm.lane_bits(), dm.state_width)
        assert layout.packs, type(model).__name__
        states = [s for s, _ in zip(_iter_states(model), range(4000))]
        assert states
        rows = np.stack([np.asarray(dm.encode(s), np.uint32)
                         for s in states])
        layout.check_fits(rows)
        assert (layout.unpack_np(layout.pack_np(rows)) == rows).all()


def _iter_states(model):
    """Host BFS enumeration (the reachable universe the packed widths
    must cover)."""
    from collections import deque

    seen = set()
    queue = deque(model.init_states())
    while queue:
        s = queue.popleft()
        if s in seen:
            continue
        seen.add(s)
        yield s
        actions = []
        model.actions(s, actions)
        for a in actions:
            nxt = model.next_state(s, a)
            if nxt is not None and model.within_boundary(nxt):
                queue.append(nxt)


def test_north_star_row_cut_at_least_2_5x():
    """ISSUE 4 acceptance: bytes_per_state on paxos check 3 (W=55)
    drops >= 2.5x under the model-derived layout."""
    from paxos import PaxosModelCfg

    dm = PaxosModelCfg(3, 3).into_model().device_model()
    layout = compile_layout(dm.lane_bits(), dm.state_width)
    assert dm.state_width == 55
    assert dm.state_width / layout.packed_width >= 2.5, layout


# -- Bit-identical parity matrix -------------------------------------------

@pytest.mark.parametrize("engine", [
    "fused",
    # tier-1 budget: the sharded pair's shard_map compiles (and, since
    # round 15, the classic sibling) ride in the slow set; the fused
    # arm stays the fast gate.
    pytest.param("classic", marks=pytest.mark.slow),
    pytest.param("sharded-fused", marks=pytest.mark.slow),
    pytest.param("sharded-classic", marks=pytest.mark.slow)])
def test_pack_arena_bit_identical_2pc(engine):
    """pack_arena on vs off: counts, discoveries, and parent maps
    identical on all four engines (the sharded pair exercises the
    packed all-to-all on the 8-device virtual mesh)."""
    model = TwoPhaseSys(4)
    runs = []
    for on in (True, False):
        c = _spawn(model, engine, 48, pack_arena=on).join()
        runs.append((c.unique_state_count(), c.state_count(),
                     frozenset(c.discoveries()), dict(c._parent_map())))
    assert runs[0] == runs[1], engine


@pytest.mark.slow  # the 2pc matrix above is the fast-set gate
@pytest.mark.parametrize("engine", ENGINES)
def test_pack_arena_bit_identical_paxos(engine):
    from paxos import PaxosModelCfg

    model = PaxosModelCfg(1, 3).into_model()
    runs = []
    for on in (True, False):
        c = _spawn(model, engine, 128, pack_arena=on).join()
        runs.append((c.unique_state_count(), c.state_count(),
                     frozenset(c.discoveries()), dict(c._parent_map())))
    assert runs[0] == runs[1], engine


@pytest.mark.slow  # round-15 tier-1 budget: the layout-roundtrip
# test above keeps these models' lane_bits contracts fast-covered.
def test_pack_arena_bit_identical_register_workloads():
    """ABD and single-copy (the other register-workload layouts) under
    a forced-packed fused run: full-enumeration counts and discoveries
    match the host reference — the CPU suite must exercise these
    layouts end to end even though the backend-aware default would
    leave them unpacked here."""
    from linearizable_register import AbdModelCfg
    from single_copy_register import SingleCopyModelCfg

    for model in (AbdModelCfg(2, 2).into_model(),
                  SingleCopyModelCfg(2, 1).into_model()):
        ref = model.checker().spawn_bfs().join()
        c = model.checker().spawn_tpu_bfs(batch_size=64,
                                          pack_arena=True).join()
        assert c._pack_on is True, type(model).__name__
        assert c.unique_state_count() == ref.unique_state_count()
        assert c.state_count() == ref.state_count()
        assert set(c.discoveries()) == set(ref.discoveries())


def test_pack_arena_no_layout_is_identity():
    """A model without lane_bits (conservative default) runs with
    pack_arena on as a no-op — same rows, same checkpoint bytes."""
    from stateright_tpu.test_util import LinearEquation

    model = LinearEquation(2, 10, 14)
    c = model.checker().spawn_tpu_bfs(batch_size=32, fused=False,
                                      pack_arena=True).join()
    assert c._pack_on is False
    assert c._Wrow == c._W


def test_pack_arena_default_is_backend_aware():
    """pack_arena=None resolves by backend, like the pipeline knob: on
    the CPU backend (this suite) the codec is pure compute overhead and
    auto means off; the forced knob still engages, and the achievable
    cut is reported either way for the bench record."""
    model = TwoPhaseSys(3)
    auto = model.checker().spawn_tpu_bfs(batch_size=64,
                                         fused=False).join()
    assert auto._pack_on is False          # CPU backend in tests
    assert auto._Wrow == auto._W
    stats = auto.scheduler_stats()["packing"]
    assert stats["enabled"] is False
    assert stats["packed_width"] < stats["state_width"]
    assert stats["packable_ratio"] > 1.0
    forced = model.checker().spawn_tpu_bfs(batch_size=64, fused=False,
                                           pack_arena=True).join()
    assert forced._pack_on is True
    assert forced.unique_state_count() == auto.unique_state_count()


# -- Cross-version checkpoint matrix ---------------------------------------

def _rewrite_header_v1(path):
    """Rewrites a v2 unpacked checkpoint into the literal v1 header
    form (no row_format keys, version 1) — a faithful old-snapshot
    fixture without keeping binary artifacts in the tree."""
    data = dict(np.load(path))
    header = json.loads(bytes(data["header"].tobytes()).decode())
    assert header.get("row_format", "u32") == "u32"
    header.pop("row_format", None)
    header.pop("lane_bits", None)
    header.pop("packed_width", None)
    header["version"] = 1
    data["header"] = np.frombuffer(json.dumps(header).encode(), np.uint8)
    np.savez_compressed(path, **data)


@pytest.mark.slow  # ~12s: the writer/reader matrix spans four engine
# spawns; test_checkpoint_format + the resilience suite cover the v3
# fast path
def test_checkpoint_cross_version_matrix(tmp_path):
    """v1 unpacked snapshots resume on packed engines, packed v2
    snapshots resume on unpacked engines (and the reverse), with
    identical continuation counts."""
    model = TwoPhaseSys(4)
    full = model.checker().spawn_bfs().join()
    want = (full.unique_state_count(), set(full.discoveries()))

    # Writer matrix: packed and unpacked mid-run snapshots.
    packed = str(tmp_path / "packed.npz")
    plain = str(tmp_path / "plain.npz")
    model.checker().target_state_count(400).spawn_tpu_bfs(
        batch_size=32, pack_arena=True, checkpoint_path=packed).join()
    model.checker().target_state_count(400).spawn_tpu_bfs(
        batch_size=32, pack_arena=False, checkpoint_path=plain).join()
    with np.load(packed) as d:
        hdr = json.loads(bytes(d["header"].tobytes()).decode())
        assert hdr["row_format"] == "packed"
        assert hdr["version"] == 2
        assert d["pending_vecs"].shape[1] == hdr["packed_width"]
    v1 = str(tmp_path / "v1.npz")
    import shutil

    shutil.copy(plain, v1)
    _rewrite_header_v1(v1)

    # Reader matrix: every stored format onto every engine format.
    for src in (packed, plain, v1):
        for on in (True, False):
            r = model.checker().spawn_tpu_bfs(
                batch_size=64, pack_arena=on, resume_from=src).join()
            got = (r.unique_state_count(), set(r.discoveries()))
            assert got == want, (src, on, got)


def test_checkpoint_packed_resumes_on_native(tmp_path):
    """The native C++ reader consumes a packed v2 snapshot via the
    self-described layout (pending_rows unpacks for it)."""
    from stateright_tpu.native.host_bfs import HOSTBFS_AVAILABLE

    if not HOSTBFS_AVAILABLE:
        pytest.skip("native extension unavailable")
    model = TwoPhaseSys(4)
    full = model.checker().spawn_bfs().join()
    ckpt = str(tmp_path / "packed.npz")
    model.checker().target_state_count(400).spawn_tpu_bfs(
        batch_size=32, pack_arena=True, checkpoint_path=ckpt).join()
    r = model.checker().spawn_native_bfs(
        model.device_model(), resume_from=ckpt).join()
    assert r.unique_state_count() == full.unique_state_count()
    assert set(r.discoveries()) == set(full.discoveries())


def test_checkpoint_resume_rejects_out_of_range_rows(tmp_path):
    """A packed engine resuming an unpacked snapshot runs the
    check_fits guard: a pending row outside the model's declared lane
    widths fails loudly instead of resuming from truncated states."""
    model = TwoPhaseSys(4)
    ckpt = str(tmp_path / "plain.npz")
    model.checker().target_state_count(400).spawn_tpu_bfs(
        batch_size=32, pack_arena=False, checkpoint_path=ckpt).join()
    data = dict(np.load(ckpt))
    assert data["pending_vecs"].shape[0] > 0
    data["pending_vecs"][0, 0] = 7  # RM lane is declared 2 bits
    # This simulates a WRITER that emitted out-of-range rows (wrong
    # model config), not disk corruption — drop the v3 integrity table
    # the in-place edit invalidated, so the check_fits guard (the
    # target of this test) is what fires.
    data.pop("crcs", None)
    np.savez_compressed(ckpt, **data)
    with pytest.raises(ValueError, match="lane 0"):
        model.checker().spawn_tpu_bfs(batch_size=32, pack_arena=True,
                                      resume_from=ckpt).join()


def test_checkpoint_newer_version_refused(tmp_path):
    from stateright_tpu.checkpoint_format import validate_header

    model = TwoPhaseSys(3)
    ckpt = str(tmp_path / "c.npz")
    model.checker().spawn_tpu_bfs(batch_size=64,
                                  checkpoint_path=ckpt).join()
    data = dict(np.load(ckpt))
    header = json.loads(bytes(data["header"].tobytes()).decode())
    header["version"] = 99
    data["header"] = np.frombuffer(json.dumps(header).encode(), np.uint8)
    with pytest.raises(ValueError, match="newer than this build"):
        validate_header(data, model_name="TwoPhaseSys",
                        state_width=6, use_symmetry=False)


# -- Telemetry -------------------------------------------------------------

def test_wave_events_carry_bandwidth_gauges(tmp_path, monkeypatch):
    from stateright_tpu.obs import SCHEMA_VERSION

    model = TwoPhaseSys(3)
    path = tmp_path / "t.jsonl"
    monkeypatch.setenv("STpu_TRACE", str(path))
    c = model.checker().spawn_tpu_bfs(batch_size=64, fused=True,
                                      pack_arena=True).join()
    monkeypatch.delenv("STpu_TRACE")
    waves = [json.loads(l) for l in path.read_text().splitlines()
             if '"wave"' in l]
    waves = [e for e in waves if e.get("type") == "wave"]
    assert waves
    layout = compile_layout(model.device_model().lane_bits(),
                            model.device_model().state_width)
    for e in waves:
        assert e["schema_version"] == SCHEMA_VERSION
        assert e["bytes_per_state"] == 4 * layout.packed_width
        assert e["arena_bytes"] > 0
        assert e["table_bytes"] == e["capacity"] * 8
    stats = c.scheduler_stats()["packing"]
    assert stats["enabled"] is True
    assert stats["packed_width"] == layout.packed_width
    assert stats["row_width"] == layout.packed_width
    assert stats["bytes_per_state"] == 4 * layout.packed_width
    assert stats["ratio"] > 1.0
    assert stats["arena_bytes_high_water"] >= max(
        e["arena_bytes"] for e in waves)
    assert stats["table_bytes_high_water"] == max(
        e["table_bytes"] for e in waves)


def test_schema_v1_wave_still_validates_and_v3_rejected():
    """trace_lint satellite: old captures validate against their own
    field set; captures from a NEWER schema fail with one clear
    upgrade message, not a field-set mismatch cascade."""
    from stateright_tpu.obs import (SCHEMA_VERSION, WAVE_FIELDS_V1,
                                    validate_event)

    v1_wave = {"type": "wave", "schema_version": 1, "engine": "classic",
               "run": "x", "wave": 0, "t": 1.0, "states": 1, "unique": 1,
               "bucket": 64, "waves": 1, "inflight": 0, "compiled": False,
               "successors": 0, "candidates": 0, "novel": 0,
               "out_rows": None, "capacity": 4096, "load_factor": 0.1,
               "overflow": False}
    assert set(v1_wave) == set(WAVE_FIELDS_V1)
    assert validate_event(v1_wave) == []
    # A v1 wave with v2 riders is NOT valid — additions go through a
    # version bump.
    bad = dict(v1_wave, bytes_per_state=8)
    assert any("unexpected" in e for e in validate_event(bad))
    newer = dict(v1_wave, schema_version=SCHEMA_VERSION + 1)
    errs = validate_event(newer)
    assert len(errs) == 1 and "newer than this validator" in errs[0]
