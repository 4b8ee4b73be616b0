"""Device forms of the register-workload examples, built on the
declarative ``RegisterWorkloadDevice`` layer: single-copy register and
the ABD quorum register. Parity gates: single-copy 93 @ 2 clients / 1
server (`single-copy-register.rs:98`) and the 2-server linearizability
counterexample (`single-copy-register.rs:118`); ABD 544 @ 2+2 on both
the single-device and sharded engines (`linearizable-register.rs:256`)."""

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "examples"))


@pytest.fixture(scope="module")
def single_copy():
    from single_copy_register import SingleCopyModelCfg

    return SingleCopyModelCfg


@pytest.fixture(scope="module")
def abd():
    from linearizable_register import AbdModelCfg

    return AbdModelCfg


def test_single_copy_device_93(single_copy):
    model = single_copy(2, 1).into_model()
    host = model.checker().spawn_bfs().join()
    tpu = model.checker().spawn_tpu_bfs(batch_size=64).join()
    assert host.unique_state_count() == 93
    assert tpu.unique_state_count() == 93
    assert set(tpu.discoveries()) == set(host.discoveries()) == \
        {"value chosen"}


def test_single_copy_device_finds_counterexample(single_copy):
    tpu = (single_copy(2, 2).into_model()
           .checker().spawn_tpu_bfs(batch_size=64).join())
    # Two servers are NOT linearizable; the on-device predicate must find
    # the counterexample, and its replayed path must prove it on host.
    path = tpu.assert_any_discovery("linearizable")
    final = path.last_state()
    assert final.history.serialized_history() is None


@pytest.mark.slow
def test_abd_device_544(abd):
    model = abd(2, 2).into_model()
    host = model.checker().spawn_bfs().join()
    tpu = model.checker().spawn_tpu_bfs(batch_size=128).join()
    assert host.unique_state_count() == 544
    assert tpu.unique_state_count() == 544
    assert set(tpu.discoveries()) == set(host.discoveries()) == \
        {"value chosen"}


@pytest.mark.slow
def test_abd_device_sharded_544(abd):
    import jax
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:8]), ("shard",))
    sharded = (abd(2, 2).into_model()
               .checker().spawn_tpu_bfs(mesh=mesh, batch_size=32).join())
    assert sharded.unique_state_count() == 544
    assert set(sharded.discoveries()) == {"value chosen"}


@pytest.mark.slow
def test_abd_device_step_differential(abd):
    """Every host-reachable state: codec round-trips and the device step
    produces exactly the host's successor set (no-op elision included)."""
    from collections import deque

    import jax
    import jax.numpy as jnp

    from stateright_tpu.fingerprint import fingerprint

    model = abd(2, 2).into_model()
    dm = model.device_model()
    step = jax.jit(dm.step)
    seen = set()
    queue = deque()
    for s in model.init_states():
        seen.add(fingerprint(s))
        queue.append(s)
    checked = 0
    while queue:
        state = queue.popleft()
        vec = dm.encode(state)
        assert fingerprint(dm.decode(vec)) == fingerprint(state)
        if checked < 60:  # cap the expensive device-vs-host comparison
            host_succ = {fingerprint(ns)
                         for _, ns in model.next_steps(state)}
            succ, valid = step(jnp.asarray(vec))
            dev_succ = {fingerprint(dm.decode(np.asarray(succ[i])))
                        for i in range(succ.shape[0]) if bool(valid[i])}
            assert dev_succ == host_succ, state
            checked += 1
        for _, ns in model.next_steps(state):
            fp = fingerprint(ns)
            if fp not in seen:
                seen.add(fp)
                queue.append(ns)
    assert len(seen) == 544


def _old_gather_server(dm, vec, dst):
    """The traced-offset slice: the reference the select form must
    equal."""
    import jax
    import jax.numpy as jnp

    nsl = len(dm.SERVER_LANES)
    start = jnp.clip(dst, 0, dm.S - 1).astype(jnp.int32) * nsl
    return jax.lax.dynamic_slice(vec, (start,), (nsl,))


def _old_scatter_server(dm, vec, dst, lanes):
    import jax
    import jax.numpy as jnp

    nsl = len(dm.SERVER_LANES)
    start = jnp.clip(dst, 0, dm.S - 1).astype(jnp.int32) * nsl
    return jax.lax.dynamic_update_slice(vec, lanes, (start,))


def _register_model(name):
    if name == "paxos-3":
        from paxos import PaxosModelCfg

        return PaxosModelCfg(3, 3).into_model()
    if name == "abd":
        from linearizable_register import AbdModelCfg

        return AbdModelCfg(2, 2).into_model()
    from single_copy_register import SingleCopyModelCfg

    return SingleCopyModelCfg(2, 3).into_model()


@pytest.mark.parametrize("name", ["paxos-3", "abd", "single-copy"])
def test_server_select_equals_traced_offset_forms(name, monkeypatch):
    """The select-over-servers gather/scatter equal the dynamic
    slice/update forms for every destination, servers and clients (a
    client clips to server S-1), and the batched step is bit-identical
    with either pair on reachable states."""
    from collections import deque

    import jax
    import jax.numpy as jnp

    from stateright_tpu.fingerprint import fingerprint

    model = _register_model(name)
    dm = model.device_model()
    nsl = len(dm.SERVER_LANES)
    rng = np.random.default_rng(24)
    for _ in range(4):
        vec = jnp.asarray(rng.integers(0, 1 << 32, dm.state_width,
                                       dtype=np.uint64).astype(np.uint32))
        lanes = jnp.asarray(rng.integers(0, 1 << 32, nsl,
                                         dtype=np.uint64).astype(np.uint32))
        servers = vec[:dm.phase_off]
        for d in range(dm.S + dm.C):
            dst = jnp.uint32(d)
            np.testing.assert_array_equal(
                dm.gather_server(vec, dst), _old_gather_server(dm, vec, dst))
            np.testing.assert_array_equal(
                dm.scatter_server(servers, dst, lanes),
                _old_scatter_server(dm, servers, dst, lanes))

    # A batch of reachable states, breadth first from the initial ones.
    states, seen, queue = [], set(), deque(model.init_states())
    while queue and len(states) < 48:
        s = queue.popleft()
        fp = fingerprint(s)
        if fp in seen:
            continue
        seen.add(fp)
        states.append(dm.encode(s))
        queue.extend(ns for _, ns in model.next_steps(s))
    batch = jnp.asarray(np.stack(states))
    succ, valid = jax.jit(jax.vmap(dm.step))(batch)
    assert bool(valid.any())
    monkeypatch.setattr(dm, "gather_server",
                        lambda v, d: _old_gather_server(dm, v, d))
    monkeypatch.setattr(dm, "scatter_server",
                        lambda v, d, l: _old_scatter_server(dm, v, d, l))
    old_succ, old_valid = jax.jit(jax.vmap(dm.step))(batch)
    np.testing.assert_array_equal(valid, old_valid)
    np.testing.assert_array_equal(succ, old_succ)
