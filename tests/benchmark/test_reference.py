"""The plain references against the upstream's published counts and
against the program, dispatch by dispatch (CPU, small sizes)."""

import pytest

from benchmark.reference import paxos, twopc


@pytest.mark.parametrize("rm_count,unique,states", [
    (3, 288, 1146),      # BASELINE row 3 (2pc.rs:128)
    (5, 8832, 58146),    # BASELINE row 4 (2pc.rs:133)
])
def test_twopc_complete_matches_the_upstream_pins(rm_count, unique,
                                                  states):
    got = twopc.make({"rm_count": rm_count}).complete()
    assert (got["unique"], got["states"]) == (unique, states)
    assert got["discoveries"] == {"abort agreement": rm_count,
                                  "commit agreement": 3 * rm_count + 1}


def test_paxos_complete_matches_the_upstream_pin():
    # BASELINE row 1 (paxos.rs:289): 16,668 unique states at 2 clients
    got = paxos.make({"client_count": 2, "server_count": 3}).complete()
    assert got["unique"] == 16668
    assert got["discoveries"] == {"value chosen": 8}


def test_waves_follow_the_queue():
    ref = twopc.make({"rm_count": 3})
    w1 = ref.waves(4, 1)  # only the init state is queued
    # init: TmAbort, then RmPrepare and RmChooseToAbort for each RM
    assert (w1["head"], w1["unique"]) == (1, 1 + 1 + 3 * 2)
    w2 = ref.waves(4, 2)
    assert w2["head"] == 5 and ref.prefix(5) == w2


def test_lossy_key_loses_states():
    exact = twopc.TwoPhaseReference(5).complete()
    lossy = twopc.TwoPhaseReference(
        5, dedup_key=lambda k: k % twopc.np.uint64(1000)).complete()
    assert lossy["unique"] < exact["unique"]


def _engine_log(model, batch, waves_per_dispatch):
    c = model.checker().spawn_tpu_bfs(
        fused=True, batch_size=batch, waves_per_dispatch=waves_per_dispatch,
        table_capacity=1 << 16, arena_capacity=1 << 16).join()
    return c


@pytest.mark.parametrize("which", ["twopc5", "paxos2"])
def test_every_dispatch_matches_the_reference(which):
    """The reference's prefix after as many waves equals the program's
    record at every dispatch: the order it takes a state's actions in
    is the program's."""
    from stateright_tpu.service.registry import default_registry

    reg = default_registry()
    if which == "twopc5":
        model, _ = reg.build("twopc", {"rm_count": 5})
        ref, batch, k = twopc.make({"rm_count": 5}), 64, 3
    else:
        model, _ = reg.build("paxos", {"client_count": 2,
                                       "server_count": 3})
        ref = paxos.make({"client_count": 2, "server_count": 3})
        batch, k = 64, 2
    c = _engine_log(model, batch, k)
    waves = head = 0
    for e in c.dispatch_log:
        waves += e["waves"]
        head += e["rows"]
        want = ref.waves(batch, waves)
        assert (head, e["unique"], e["states"]) == (
            want["head"], want["unique"], want["states"]), waves
    depths = {n: len(p.into_states()) - 1
              for n, p in c.discoveries().items()}
    assert depths == ref.prefix(head)["discoveries"]
