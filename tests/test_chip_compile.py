"""Ahead-of-time compiles of the chip's programs against a described
``v5e:2x2``: the TPU compiler is installed here and refuses what the
chip would refuse (64-bit collectives, Mosaic operand types, memory),
which interpret mode and the CPU backend cannot show. Nothing runs.

The engines are built on the CPU at a tiny size, then asked to compile
their program for the described chip at the shapes ``chip_smoke.py``
reaches. ``pack_arena=True`` is passed because that is the default on
a TPU (the CPU default is off). The topology is described inside a
fixture only, never at import (one process at a time may load libtpu).
"""

import hashlib
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, SingleDeviceSharding

import chip_smoke
from benchmark.trace_stages import STAGES, stage_of
from stateright_tpu.tpu import engine
from stateright_tpu.tpu.sharded_fused import exchange_bucket_rows
from two_phase_commit import TwoPhaseSys


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _for_chip(sharding):
    """An ``_aot`` replacement that compiles for the described chip and
    lets the compiler's refusal propagate (the engine's own ``_aot``
    keeps a lazy fallback)."""
    def aot(jitted, specs):
        if sharding is not None:
            specs = [jax.ShapeDtypeStruct(s.shape, s.dtype,
                                          sharding=sharding)
                     for s in specs]
        return jitted.lower(*specs).compile()
    return aot


def _engine(model, **kw):
    return (model.checker().target_state_count(1)
            .spawn_tpu_bfs(pack_arena=True, **kw).join())


def test_classic_wave_paxos3_compiles(one_chip):
    # B=2048: at B=4096 this compile took 29 s idle and 54 s under the
    # suite's -n 6 load, too near the 75 s per-test budget. The fused
    # case below compiles the same wave body at B=4096.
    c = _engine(chip_smoke.paxos3(), fused=False, batch_size=64)
    c._aot = _for_chip(one_chip)
    prog = c._wave_fn(1 << 21, 2048)
    assert prog.memory_analysis().temp_size_in_bytes > 0


#: the single-chip fused dispatch programs, at the sizes their cells
#: run (paxos3-check, twopc10-check) or at a small size (twopc3)
FUSED = {"paxos3": (chip_smoke.paxos3, (4096, 1 << 22, 1 << 21)),
         "twopc3": (lambda: TwoPhaseSys(3), (1024, 1 << 16, 1 << 16)),
         "twopc10": (lambda: TwoPhaseSys(10), (4096, 1 << 27, 1 << 26))}

#: sha256 of each cell's fused dispatch compiled for one described v5e
#: chip, its text without metadata (``_hlo_digest``): the program as the
#: chunked probe left it. A change to the single-chip wave changes
#: these on purpose.
FUSED_HLO_SHA256 = {
    "paxos3": ("7fe0aeeed73766fad593b9e7f0941ebf"
                "f0e6aa079a241b2bff47419b1448b64b"),
    "twopc10": ("627d1e29c014eba15882a4b72148feaa"
                "3fd2d4a42b62a3b54ff8274d60b588a9"),
}

#: the stack-frame tables at the head of a compiled module's text
_STACK_TABLES = ("FileNames", "FunctionNames", "FileLocations",
                 "StackFrames")


def _hlo_digest(text: str) -> str:
    """A compiled module's text without its metadata (op names, source
    lines, stack frames), hashed."""
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    text = "\n\n".join(b for b in text.split("\n\n")
                       if not b.startswith(_STACK_TABLES))
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.fixture(scope="module")
def fused_programs(one_chip):
    """Each FUSED dispatch compiled for the described chip (compiled
    once here: the larger ones take 20-30 s)."""
    out = {}
    for name, (make, shape) in FUSED.items():
        c = _engine(make(), fused=True, batch_size=64)
        c._aot = _for_chip(one_chip)
        out[name] = c._build_dispatch_fn(*shape)
    return out


@pytest.mark.parametrize("name", ["paxos3", "twopc3"])
def test_fused_dispatch_compiles(fused_programs, name):
    mem = fused_programs[name].memory_analysis()
    # The arena and table are donated: the compiler aliases them.
    assert mem.alias_size_in_bytes > 0
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


@pytest.mark.parametrize("name", sorted(FUSED_HLO_SHA256))
def test_fused_dispatch_hlo_is_unchanged(fused_programs, name):
    """The single-chip cells' programs, modulo metadata, are the ones
    the chunked probe made: a later change that was meant to leave the
    single-chip wave alone shows here."""
    assert _hlo_digest(fused_programs[name].as_text()) == (
        FUSED_HLO_SHA256[name])


def test_sharded_fused_dispatch_compiles_on_four_chips(topo):
    c = _engine(chip_smoke.paxos3(), fused=True, batch_size=32,
                mesh=Mesh(np.array(jax.devices()[:4]), ("shard",)))
    # Recompile the same engine for the described 2x2 mesh; its specs
    # carry NamedSharding over the engine's mesh.
    c._mesh = Mesh(np.array(topo.devices), ("shard",))
    c._wave_cache.clear()
    c._aot = _for_chip(None)
    prog = c._dispatch_fn(256, 1 << 16, 1 << 16)
    text = prog.as_text()
    assert "all-to-all" in text and "all-reduce" in text


#: 2pc-11 on four chips at the twopc11-check-4chip cell's per-shard
#: sizes (benchmark/configs/2pc-11.json): batch, table, arena
TWOPC11_SHARD = (4096, 1 << 28, 1 << 27)


@pytest.fixture(scope="module")
def twopc11_mesh_program(topo):
    """The cell's sharded-fused dispatch, compiled for the described
    2x2 mesh (about 40 s here)."""
    c = _engine(TwoPhaseSys(11), fused=True, batch_size=32,
                mesh=Mesh(np.array(jax.devices()[:4]), ("shard",)))
    c._mesh = Mesh(np.array(topo.devices), ("shard",))
    c._wave_cache.clear()
    c._aot = _for_chip(None)
    return c._dispatch_fn(*TWOPC11_SHARD)


def _ops(text: str, kind: str) -> list:
    """The name-scope path of each ``kind`` instruction of a compiled
    module's text."""
    return re.findall(r"= [^\n]*? " + kind
                      + r'\([^\n]*?metadata=\{op_name="([^"]*)"', text)


def _probe_loop_gather_rows(text: str) -> set:
    """The leading dimension of every gather inside the ``probe``
    scope's loops."""
    return {int(rows) for rows, path in re.findall(
        r"= [a-z0-9]+\[(\d+)[^\n]*? gather\([^\n]*?"
        r'metadata=\{op_name="([^"]*)"', text)
        if "probe/while" in path}


def test_fused_probe_loop_carries_one_chunk(fused_programs):
    """The twopc10-check wave's probe rounds gather a chunk of compacted
    candidates, not the wave's B*F rows."""
    assert _probe_loop_gather_rows(fused_programs["twopc10"].as_text()) == {
        engine.PROBE_CHUNK}


#: sha256 of the twopc11-check-4chip dispatch compiled for the
#: described 2x2, its text without metadata (``_hlo_digest``): the
#: program as the sized exchange buckets left it
TWOPC11_MESH_HLO_SHA256 = ("6e71a2eef168b3a88f6d496bfe702eb0"
                           "9e2ae0a24efaffd2386e12bdf713d847")


def test_twopc11_mesh_dispatch_hlo_is_unchanged(twopc11_mesh_program):
    """The mesh cell's program, modulo metadata: a later change that was
    meant to leave the sharded-fused wave alone shows here."""
    assert _hlo_digest(twopc11_mesh_program.as_text()) == (
        TWOPC11_MESH_HLO_SHA256)


def test_twopc11_mesh_dispatch_fits_a_chip(twopc11_mesh_program):
    mem = twopc11_mesh_program.memory_analysis()
    # per chip: a 2^28-slot table slice and a 2^27-row arena slice
    assert mem.argument_size_in_bytes > 5e9
    assert mem.alias_size_in_bytes > 5e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


def test_twopc11_mesh_dispatch_names_its_stages(twopc11_mesh_program):
    text = twopc11_mesh_program.as_text()
    parts = {p for path in re.findall(r'op_name="([^"]*)"', text)
             for p in path.split("/")}
    assert set(STAGES) | {"exchange"} <= parts


def test_twopc11_mesh_wave_has_one_local_dedup_loop(twopc11_mesh_program):
    """A wave's edge in a trace is its one top-level ``while`` in scope
    ``local_dedup`` (``benchmark/trace_stages.py``): the owner's, one a
    trip of the dispatch loop, which is one exchange round (a whole
    wave where its buckets fit one round). The sender side's duplicate
    collapse runs under ``exchange``."""
    loops = _ops(twopc11_mesh_program.as_text(), "while")
    assert [p for p in loops if stage_of(p) == "local_dedup"] == [
        "jit(local)/shard_map/while/body/local_dedup/while"]
    assert any("exchange" in p.split("/") and stage_of(p) is None
               for p in loops)


def test_twopc11_mesh_probe_loop_carries_one_chunk(twopc11_mesh_program):
    """An owner's probe rounds gather a chunk of its received rows'
    candidates, not all n*B*F of them."""
    assert _probe_loop_gather_rows(twopc11_mesh_program.as_text()) == {
        engine.PROBE_CHUNK}


def test_twopc11_mesh_all_to_alls_carry_one_bucket_per_owner(
        twopc11_mesh_program):
    """Every all-to-all carries the n*CAP rows of one exchange round,
    CAP the balanced share of a shard's B*F successors, not n*B*F; and
    the buckets are gathered: no scatter sits under ``exchange`` but the
    sender-side collapse loop's own."""
    n, (batch, _, _) = 4, TWOPC11_SHARD
    succ = batch * TwoPhaseSys(11).device_model().max_fanout
    cap = exchange_bucket_rows(succ, n)
    assert cap == succ // n
    text = twopc11_mesh_program.as_text()
    shapes = re.findall(r"= [a-z0-9]+\[([\d,]+)\][^\n]*? all-to-all\(",
                        text)
    assert len(shapes) >= 5
    for dims in shapes:
        dims = [int(d) for d in dims.split(",")]
        assert dims[0] == n and cap in dims[1:], dims
    scatters = [p for p in _ops(text, "scatter")
                if "exchange" in p.split("/")]
    assert all("/exchange/while/" in p for p in scatters), scatters


def test_twopc11_mesh_all_to_alls_sit_in_exchange(twopc11_mesh_program):
    paths = _ops(twopc11_mesh_program.as_text(), "all-to-all")
    assert len(paths) >= 5
    assert all("exchange" in p.split("/") and stage_of(p) is None
               for p in paths)


#: BASELINE config 5 as the paxos4-sym-live-check cell builds it
#: (benchmark/configs/paxos-4-sym-live.json)
PAXOS4_SYM_LIVE = {"client_count": 4, "server_count": 3,
                   "liveness": True, "symmetry": True}


@pytest.fixture(scope="module")
def paxos4_sym_live_program(one_chip):
    """The cell's fused dispatch compiled for the described chip, at
    B=512 (the cell runs 4096; the loop structure does not depend on
    B, and 4096 takes about 110 s here) and the cell's table and
    arena."""
    from stateright_tpu.service.registry import default_registry

    model, _ = default_registry().build("paxos", PAXOS4_SYM_LIVE)
    c = _engine(model, fused=True, batch_size=64)
    assert c._use_symmetry
    c._aot = _for_chip(one_chip)
    return c._build_dispatch_fn(512, 1 << 22, 1 << 21)


def test_paxos4_sym_live_wave_has_one_local_dedup_loop(
        paxos4_sym_live_program):
    """A wave's edge in a trace is its one top-level ``while`` in scope
    ``local_dedup`` (``benchmark/trace_stages.py``): the symmetry
    representative and the eventually bits add none."""
    loops = _ops(paxos4_sym_live_program.as_text(), "while")
    assert [p for p in loops if stage_of(p) == "local_dedup"] == [
        "jit(dispatch)/while/body/local_dedup/while"]


def test_paxos3_step_compiles_without_loops(one_chip):
    """The register workload's server gather/scatter select over the
    static servers: a traced-offset slice or update under ``vmap``
    becomes a batched gather/scatter, which the TPU compiler turns into
    a serial ``while`` over the 73,728 successor rows of a B=4096 wave."""
    dm = chip_smoke.paxos3().device_model()
    spec = jax.ShapeDtypeStruct((4096, dm.state_width), jnp.uint32,
                                sharding=one_chip)
    text = jax.jit(jax.vmap(dm.step)).lower(spec).compile().as_text()
    assert "while(" not in text
