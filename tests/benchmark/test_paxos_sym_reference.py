"""The plain reference for BASELINE config 5 (``reference/paxos_sym.py``):
client symmetry and "eventually chosen", on its own terms and against
the program, dispatch by dispatch (CPU, small sizes)."""

import pytest

from benchmark.reference import paxos, paxos_sym

CONFIG5 = {"client_count": 4, "server_count": 3, "liveness": True,
           "symmetry": True}


def _sample(ref, n_states=1500, stride=7):
    """Reachable states of ``ref``, breadth first."""
    ref.ensure_expanded(n_states)
    return ref.queue[:n_states:stride]


def test_the_group_is_the_residue_classes():
    assert paxos_sym.client_permutations(2, 3) == [(0, 1)]
    assert paxos_sym.client_permutations(3, 3) == [(0, 1, 2)]
    assert paxos_sym.client_permutations(4, 3) == [(0, 1, 2, 3),
                                                   (3, 1, 2, 0)]


def test_a_trivial_group_counts_as_plain():
    # at 2 clients no two clients share a residue mod 3
    got = paxos_sym.make(dict(CONFIG5, client_count=2)).complete()
    plain = paxos.make({"client_count": 2, "server_count": 3}).complete()
    assert got["unique"] == plain["unique"] == 16668
    assert got["states"] == plain["states"]
    # single-shot clients on a perfect network always see a value
    assert got["discoveries"] == {"value chosen": 8}


def test_the_orbit_key_is_invariant_under_the_swap():
    ref = paxos_sym.make(CONFIG5)
    (swap,) = ref.maps
    moved = 0
    for s in _sample(paxos_sym.make(dict(CONFIG5, symmetry=False))):
        image = swap.state(s)
        assert swap.state(image) == s  # an involution
        assert ref.orbit(image) == ref.orbit(s) == {s, image}
        moved += image != s
    assert moved > 0


def test_the_swap_commutes_with_the_successors():
    """The group acts by automorphisms: the images of a state's
    successors are its image's successors."""
    ref = paxos_sym.make(dict(CONFIG5, symmetry=False))
    swap = paxos_sym.make(CONFIG5).maps[0]
    for s in _sample(ref, 600, 11):
        assert (set(swap.state(t) for t in ref.successors(s))
                == set(ref.successors(swap.state(s))))


def _hand_built(ref, net, client, history=(True, (), ())):
    """``ref`` started from one state: initial servers, every client in
    state ``client``, the network ``net``."""
    server = ((0, 0), None, (), (), None, False)
    state = ((server,) * ref.S, (client,) * ref.C, frozenset(net),
             history)
    ref.init = lambda: state
    return ref


#: client 3's Get (request 6) in flight, and its write of "A" returned
READING = ((3, ((), ("R",))),)
WROTE = ((3, (((), ("W", "A"), ("WOk",)),)),)


def test_a_terminal_state_that_never_chose_is_discovered():
    ref = _hand_built(paxos_sym.make(dict(CONFIG5, client_count=2)),
                      net=(), client=(None, 2))
    got = ref.complete()
    assert (got["unique"], got["states"]) == (1, 1)
    assert got["discoveries"] == {"eventually chosen": 0}


def test_a_chosen_value_clears_the_bit_for_what_follows():
    # a GetOk with a value clears the bit at the state that holds it;
    # delivering it ends in a terminal state that inherits the cleared
    # bit
    live = paxos_sym.make(dict(CONFIG5, client_count=2))
    got = _hand_built(live, net=[(0, 3, ("GetOk", 6, "A"))],
                      client=(6, 1),
                      history=(True, WROTE, READING)).complete()
    assert (got["unique"], got["states"]) == (2, 2)
    assert got["discoveries"] == {"value chosen": 0}
    # the same terminal state reached without a chosen value on its path
    bare = paxos_sym.make(dict(CONFIG5, client_count=2))
    got = _hand_built(bare, net=[(0, 3, ("GetOk", 6, paxos.NO_VALUE))],
                      client=(6, 1),
                      history=(True, (), READING)).complete()
    assert got["discoveries"] == {"eventually chosen": 1}


def test_without_liveness_nothing_is_eventually():
    ref = _hand_built(paxos_sym.make(dict(CONFIG5, client_count=2,
                                          liveness=False)),
                      net=(), client=(None, 2))
    assert ref.complete()["discoveries"] == {}


@pytest.mark.parametrize("batch,waves_per_dispatch", [(64, 2), (256, 3)])
def test_every_dispatch_matches_the_program(batch, waves_per_dispatch):
    """Config 5 on the fused engine, built through the registry as the
    benchmark builds it: at every dispatch its expanded rows, orbits and
    generated states are the reference's after as many waves, and its
    ``eventually_pending`` is the count of the rows it admitted whose
    eventually bit the reference left set."""
    from stateright_tpu.service.registry import default_registry

    model, _ = default_registry().build("paxos", CONFIG5)
    c = model.checker().target_state_count(12 * batch * 23).spawn_tpu_bfs(
        fused=True, batch_size=batch,
        waves_per_dispatch=waves_per_dispatch,
        table_capacity=1 << 16, arena_capacity=1 << 16).join()
    ref = paxos_sym.make(CONFIG5)
    waves = head = 0
    admitted = 1  # the initial state, seeded before the first dispatch
    assert len(c.dispatch_log) > 3
    for e in c.dispatch_log:
        waves += e["waves"]
        head += e["rows"]
        want = ref.waves(batch, waves)
        assert (head, e["unique"], e["states"]) == (
            want["head"], want["unique"], want["states"]), waves
        assert e["eventually_pending"] == sum(
            ref.ebits[admitted:want["unique"]]), waves
        admitted = want["unique"]
    assert sum(e["sym_folded"] for e in c.dispatch_log) > 0
    pending = sum(e["eventually_pending"] for e in c.dispatch_log)
    assert 0 < pending < admitted - 1
    depths = {n: len(p.into_states()) - 1
              for n, p in c.discoveries().items()}
    assert depths == ref.prefix(head)["discoveries"]
