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

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, SingleDeviceSharding

import chip_smoke
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


@pytest.mark.parametrize("name", ["paxos3", "twopc3"])
def test_fused_dispatch_compiles(one_chip, name):
    if name == "paxos3":
        model, sizes = chip_smoke.paxos3(), chip_smoke.PAXOS3_ONE_CHIP
        shape = (sizes["batch_size"], sizes["table_capacity"],
                 sizes["arena_capacity"])
    else:
        model, shape = TwoPhaseSys(3), (1024, 1 << 16, 1 << 16)
    c = _engine(model, fused=True, batch_size=64)
    c._aot = _for_chip(one_chip)
    prog = c._build_dispatch_fn(*shape)
    mem = prog.memory_analysis()
    # The arena and table are donated: the compiler aliases them.
    assert mem.alias_size_in_bytes > 0
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


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


def test_pallas_table_kernel_is_refused_on_tpu(one_chip, monkeypatch):
    """The PR-21 verdict: Mosaic has no 64-bit vectors, so neither the
    table kernel nor any kernel with uint64 operands compiles for the
    chip, and the engines refuse the kernels on a TPU instead of
    running interpret mode or switching to XLA."""
    from jax.experimental import pallas as pl

    from stateright_tpu.tpu import pallas_table

    def spec(n):
        return jax.ShapeDtypeStruct((n,), jnp.uint64, sharding=one_chip)

    cap = 1 << 12
    table = jax.jit(lambda fps, vis: pallas_table.dedup_and_insert_pallas(
        fps, vis, cap, interpret=False))
    with pytest.raises(Exception):
        table.lower(spec(1024), spec(cap)).compile()

    def add(x_ref, o_ref):
        o_ref[:] = x_ref[:] + x_ref[:]

    plain = jax.jit(lambda x: pl.pallas_call(
        add, out_shape=jax.ShapeDtypeStruct((1024,), jnp.uint64))(x))
    with pytest.raises(Exception, match="X64 element types"):
        plain.lower(spec(1024)).compile()

    monkeypatch.setattr(pallas_table, "_BACKEND_DECISION_CACHE", [False])
    for knobs in ({"wave_kernel": True}, {"table_impl": "pallas"}):
        with pytest.raises(NotImplementedError,
                           match="cannot run on a TPU"):
            TwoPhaseSys(3).checker().spawn_tpu_bfs(fused=True, **knobs)


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
