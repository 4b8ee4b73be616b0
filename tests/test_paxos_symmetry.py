"""Client-exchangeability symmetry for the paxos workload (driver
config 5: "paxos check 4 + symmetry reduction + liveness").

The reference's paxos example has no symmetry arm, so there is no
reference pin; the orbit counts here are pinned by cross-engine
agreement (Python DFS / device BFS / native C++ DFS share the partition
by construction — same encoding, same rewrite maps) plus the structural
invariants below. Derivation (register_workload.py sym section): client
destinations are index-derived mod S (`register.rs:169-196`), so the
group is the product of symmetric groups over client residue classes —
trivial below 4 clients at 3 servers, exactly {id, swap(client 0,
client 3)} at 4.

Pinned at 4 clients (MEASUREMENTS.md round 5):

- full space 2,372,188 unique states (round 4, three-way agreement)
- orbits 1,194,428 => sigma-fixed states 2*1,194,428 - 2,372,188
  = 16,668 (orbit counting: fixed = 2*orbits - total for a 2-group)
"""

import itertools
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "examples"))

from paxos import PaxosModelCfg

C4_ORBITS = 1_194_428
C4_TOTAL = 2_372_188  # pinned round 4 (MEASUREMENTS.md three-way gate)


def _model(c, liveness=False):
    return PaxosModelCfg(c, 3, liveness=liveness).into_model()


def _reachable_sample(model, n_states=1500, stride=7):
    from collections import deque

    seen, q = {}, deque()
    for s in model.init_states():
        seen[s] = None
        q.append(s)
    while q and len(seen) < n_states:
        s = q.popleft()
        for _, s2 in model.next_steps(s):
            if s2 is not None and s2 not in seen:
                seen[s2] = None
                q.append(s2)
    return list(itertools.islice(seen, 0, n_states, stride))


def test_group_is_trivial_below_4_clients():
    for c in (1, 2, 3):
        dm = _model(c).device_model()
        assert dm.client_permutations() == []
    dm4 = _model(4).device_model()
    assert dm4.client_permutations() == [(3, 1, 2, 0)]


def test_rewrite_involution_codec_and_commutation():
    """The transposition rewrite must be an involution, land inside the
    codec's range (decode->encode round-trips), and commute with the
    host model's successor function (the automorphism property that
    makes the reduction sound)."""
    model = _model(4)
    dm = model.device_model()
    (t,) = dm._sym_tables()
    states = _reachable_sample(model)
    assert len(states) > 100
    for s in states:
        vec = np.asarray(dm.encode(s), np.uint32)
        r = np.asarray(dm._sym_rewrite(vec, t, np), np.uint32)
        rr = np.asarray(dm._sym_rewrite(r, t, np), np.uint32)
        assert np.array_equal(rr, vec), "rewrite is not an involution"
        assert np.array_equal(
            np.asarray(dm.encode(dm.decode(r)), np.uint32), r), \
            "rewrite left the codec range"
    for s in states[:20]:
        vec = np.asarray(dm.encode(s), np.uint32)
        r = np.asarray(dm._sym_rewrite(vec, t, np), np.uint32)
        succ_orig = sorted(
            np.asarray(dm._sym_rewrite(
                np.asarray(dm.encode(x), np.uint32), t, np),
                np.uint32).tobytes()
            for _, x in model.next_steps(s) if x is not None)
        succ_rewr = sorted(
            np.asarray(dm.encode(x), np.uint32).tobytes()
            for _, x in model.next_steps(dm.decode(r)) if x is not None)
        assert succ_orig == succ_rewr, \
            "rewrite does not commute with step (not an automorphism)"


def test_host_and_device_representative_agree():
    import jax.numpy as jnp

    model = _model(4)
    dm = model.device_model()
    for s in _reachable_sample(model, n_states=400, stride=11):
        vec_h = np.asarray(dm.encode(dm.host_representative(s)), np.uint32)
        vec_d = np.asarray(
            dm.representative(jnp.asarray(dm.encode(s))), np.uint32)
        assert np.array_equal(vec_h, vec_d)


def test_trivial_group_counts_match_plain_check_native():
    """At 2 clients the group is trivial: check-sym == check exactly."""
    model = _model(2)
    checker = (model.checker().symmetry()
               .spawn_native_dfs(model.device_model()).join())
    assert checker.unique_state_count() == 16_668


def test_c4_orbits_native():
    """The flagship gate: full 4-client space under symmetry on the
    native C++ DFS (seconds)."""
    model = _model(4)
    checker = (model.checker().symmetry()
               .spawn_native_dfs(model.device_model()).join())
    assert checker.unique_state_count() == C4_ORBITS
    assert set(checker.discoveries()) == {"value chosen"}


def test_c4_orbits_native_liveness():
    """Driver config 5 exactly: 4 clients + symmetry + the eventually
    property. The liveness property holds on the full enumeration
    (single-shot clients on a perfect network cannot wedge), so the only
    discovery stays "value chosen"."""
    model = _model(4, liveness=True)
    checker = (model.checker().symmetry()
               .spawn_native_dfs(model.device_model()).join())
    assert checker.unique_state_count() == C4_ORBITS
    assert set(checker.discoveries()) == {"value chosen"}


def test_orbit_equation():
    """For the 2-element group, |orbits| = (|states| + |fixed|) / 2 with
    |fixed| >= 0 and consistent with the pinned totals."""
    fixed = 2 * C4_ORBITS - C4_TOTAL
    assert 0 <= fixed <= C4_TOTAL
    assert fixed == 16_668


def test_single_copy_symmetry_47_orbits_all_engines():
    """At 1 server every client shares residue class 0: the full
    symmetric group applies. Pin: 47 orbits of the 93-state space at 2
    clients (one sigma-fixed state), agreed by the Python DFS (host
    representative), fused device BFS, and native DFS."""
    from single_copy_register import SingleCopyModelCfg

    model = SingleCopyModelCfg(2, 1).into_model()
    dm = model.device_model()
    py = (model.checker().symmetry_fn(dm.host_representative)
          .spawn_dfs().join())
    dev = model.checker().symmetry().spawn_tpu_bfs().join()
    nat = (model.checker().symmetry()
           .spawn_native_dfs(model.device_model()).join())
    assert (py.unique_state_count() == dev.unique_state_count()
            == nat.unique_state_count() == 47)


def test_a_symmetric_model_is_checked_under_symmetry_everywhere():
    """A model that declares itself ``symmetric`` is checked under
    symmetry by engines built from its ``checker()`` and by the elastic
    checker, which builds from the model itself: the 47 orbits of the
    single-copy space at 2 clients and 1 server, not its 93 states."""
    from single_copy_register import SingleCopyModelCfg

    from stateright_tpu.resilience.elastic import ElasticChecker

    def model():
        m = SingleCopyModelCfg(2, 1).into_model()
        m.symmetric = True
        return m

    assert model().checker()._symmetry is not None
    dev = model().checker().spawn_tpu_bfs().join()
    elastic = ElasticChecker(model, workers=1, n_partitions=2,
                             batch_rows=64, transport="thread").join()
    assert dev.unique_state_count() == elastic.unique_state_count() == 47


def test_single_copy_commutation():
    """The automorphism property for the single-copy rewrite."""
    from single_copy_register import SingleCopyModelCfg

    model = SingleCopyModelCfg(2, 1).into_model()
    dm = model.device_model()
    (t,) = dm._sym_tables()
    for s in _reachable_sample(model, n_states=93, stride=1):
        vec = np.asarray(dm.encode(s), np.uint32)
        r = np.asarray(dm._sym_rewrite(vec, t, np), np.uint32)
        assert np.array_equal(
            np.asarray(dm._sym_rewrite(r, t, np), np.uint32), vec)
        succ_orig = sorted(
            np.asarray(dm._sym_rewrite(
                np.asarray(dm.encode(x), np.uint32), t, np),
                np.uint32).tobytes()
            for _, x in model.next_steps(s) if x is not None)
        succ_rewr = sorted(
            np.asarray(dm.encode(x), np.uint32).tobytes()
            for _, x in model.next_steps(dm.decode(r)) if x is not None)
        assert succ_orig == succ_rewr


def test_abd_symmetry_trivial_and_ambiguity_guard():
    """Every device-encodable ABD config has a trivial client group
    (nontrivial ones collide on request-id products and are rejected);
    check-sym == check at 2+2, and 3 clients / 2 servers degrades to
    the host engine with the ambiguity warning."""
    from linearizable_register import AbdModelCfg

    model = AbdModelCfg(2, 2).into_model()
    assert model.device_model().client_permutations() == []
    nat = (model.checker().symmetry()
           .spawn_native_dfs(model.device_model()).join())
    assert nat.unique_state_count() == 544

    bad = AbdModelCfg(3, 2).into_model()
    with pytest.warns(RuntimeWarning, match="request ids collide"):
        checker = bad.checker().target_state_count(200).spawn_tpu_bfs()
    checker.join()
    assert type(checker).__name__ == "BfsChecker"  # host fallback


@pytest.mark.slow
def test_c2_symmetry_device_parity():
    """Trivial-group plumbing through the fused device engine."""
    model = _model(2)
    checker = model.checker().symmetry().spawn_tpu_bfs().join()
    assert checker.unique_state_count() == 16_668


@pytest.mark.slow
def test_c2_symmetry_python_dfs():
    """Trivial-group plumbing through the Python DFS via the shared
    host representative."""
    model = _model(2)
    dm = model.device_model()
    checker = (model.checker().symmetry_fn(dm.host_representative)
               .spawn_dfs().join())
    assert checker.unique_state_count() == 16_668


@pytest.mark.slow
def test_c4_orbits_device():
    """Cross-engine orbit gate: the fused device BFS (different
    traversal order, different canonical-member choice path) must count
    the same orbits as the native DFS."""
    model = _model(4)
    checker = model.checker().symmetry().spawn_tpu_bfs(
        batch_size=4096, table_capacity=1 << 22).join()
    assert checker.unique_state_count() == C4_ORBITS


def test_single_copy_sigma_fixed_counted_directly():
    """Closes the Burnside loop on the small nontrivial group without
    relying on the orbit equation: enumerate the RAW space with the
    fused engine, apply the non-identity client permutation to every
    arena row, and count exact fixed points. 93 raw states, 47 orbits
    => exactly 2*47 - 93 = 1 sigma-fixed state. (The C=4 paxos analog
    — 16,668 fixed of 2,372,188, measured the same way — is recorded in
    MEASUREMENTS.md; it runs minutes, this runs milliseconds.)"""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from single_copy_register import SingleCopyModelCfg

    model = SingleCopyModelCfg(2, 1).into_model()
    dm = model.device_model()
    tables = [t for t in dm._sym_tables()
              if tuple(t["sigma"]) != tuple(range(dm.C))]
    assert len(tables) == 1, "2 clients on 1 server: one swap"
    c = model.checker().spawn_tpu_bfs(fused=True).join()
    assert c.unique_state_count() == 93
    vecs = c._unpack_np(np.asarray(c._arena[0])[:c._arena_tail])
    sv = np.asarray(jax.jit(jax.vmap(
        lambda v: dm._sym_rewrite(v, tables[0], jnp)))(jnp.asarray(vecs)))
    fixed = int((sv == vecs).all(axis=1).sum())
    assert fixed == 1
    # Burnside, with every term measured independently:
    assert (93 + fixed) // 2 == 47


@pytest.mark.slow
def test_c4_raw_full_space_fused_and_direct_sigma_fixed():
    """The fused DEVICE engine's full raw C=4 enumeration (~70 s on the
    CPU backend post round-5 optimizations — it was a 6.5-minute
    measurement, not a gate, in round 4), plus the direct Burnside
    closure: apply the client swap to every arena row and count exact
    fixed points. Pins all three independently-measured terms of
    (|states| + |fixed|) / 2 = |orbits|."""
    import jax
    import jax.numpy as jnp

    model = PaxosModelCfg(4, 3).into_model()
    dm = model.device_model()
    c = model.checker().spawn_tpu_bfs(
        batch_size=1024, table_capacity=1 << 23,
        arena_capacity=1 << 22, fused=True).join()
    assert c.unique_state_count() == C4_TOTAL
    assert set(c.discoveries()) == {"value chosen"}
    vecs = c._unpack_np(np.asarray(c._arena[0])[:c._arena_tail])
    assert len(vecs) == C4_TOTAL
    sigma = [t for t in dm._sym_tables()
             if tuple(t["sigma"]) != tuple(range(dm.C))]
    assert len(sigma) == 1
    j_s = jax.jit(jax.vmap(lambda v: dm._sym_rewrite(v, sigma[0], jnp)))
    j_rep = jax.jit(jax.vmap(dm.representative))
    fixed = reps = 0
    for i in range(0, len(vecs), 1 << 16):
        chunk = vecs[i:i + (1 << 16)]
        fixed += int((np.asarray(j_s(jnp.asarray(chunk)))
                      == chunk).all(axis=1).sum())
        reps += int((np.asarray(j_rep(jnp.asarray(chunk)))
                     == chunk).all(axis=1).sum())
    assert fixed == 2 * C4_ORBITS - C4_TOTAL == 16_668
    assert reps == C4_ORBITS


def test_registry_builds_config_5_with_symmetry_on():
    """``symmetry`` and ``liveness`` reach the engine through the model
    registry, as the job service and the benchmark build models: at 4
    clients the symmetric check admits fewer states than the raw one
    after the same waves. The old parameter sets build as before."""
    from stateright_tpu.service.registry import default_registry

    reg = default_registry()
    logs = {}
    for sym in (False, True):
        model, params = reg.build("paxos", {"client_count": 4,
                                            "server_count": 3,
                                            "liveness": True,
                                            "symmetry": sym})
        assert params["symmetry"] is sym
        assert "eventually chosen" in [p.name for p in model.properties()]
        c = model.checker().target_state_count(3000).spawn_tpu_bfs(
            fused=True, batch_size=64, waves_per_dispatch=4,
            table_capacity=1 << 14, arena_capacity=1 << 14).join()
        assert c._use_symmetry is sym
        logs[sym] = c.dispatch_log
    raw, sym = logs[False][0], logs[True][0]
    assert raw["waves"] == sym["waves"]
    assert sym["unique"] < raw["unique"]
    assert sym["sym_folded"] > 0 and raw["sym_folded"] is None
    assert raw["eventually_pending"] == raw["novel"]

    model, params = reg.build("paxos", {"client_count": 2,
                                        "server_count": 3})
    assert params == {"client_count": 2, "server_count": 3,
                      "liveness": False, "symmetry": False}
    assert model.checker()._symmetry is None
    assert [p.name for p in model.properties()] == ["linearizable",
                                                    "value chosen"]
