"""Single Decree Paxos, checked for linearizability.

Counterpart of the reference's `examples/paxos.rs`: servers implement the
two Paxos phases behind the ``RegisterMsg`` Put/Get interface; clients are
``RegisterActor.client``s; the ``LinearizabilityTester`` rides along as
ActorModel history. Parity: 16,668 unique states @ 2 clients / 3 servers.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dataclasses import dataclass, replace
from typing import Optional, Tuple

from stateright_tpu import Expectation
from stateright_tpu.actor import Actor, ActorModel, Id, Out, model_peers, majority
from stateright_tpu.actor.register import (
    Get, GetOk, Internal, Put, PutOk, RegisterActor,
    record_invocations, record_returns)
from stateright_tpu.semantics import LinearizabilityTester, Register

# Ballot = (round, leader_id); Proposal = (request_id, requester_id, value)
NO_VALUE = "\x00"


@dataclass(frozen=True)
class Prepare:
    ballot: Tuple

    def __repr__(self):
        return f"Prepare {{ ballot: {self.ballot!r} }}"


@dataclass(frozen=True)
class Prepared:
    ballot: Tuple
    last_accepted: Optional[Tuple]

    def __repr__(self):
        return (f"Prepared {{ ballot: {self.ballot!r}, "
                f"last_accepted: {self.last_accepted!r} }}")


@dataclass(frozen=True)
class Accept:
    ballot: Tuple
    proposal: Tuple

    def __repr__(self):
        return (f"Accept {{ ballot: {self.ballot!r}, "
                f"proposal: {self.proposal!r} }}")


@dataclass(frozen=True)
class Accepted:
    ballot: Tuple

    def __repr__(self):
        return f"Accepted {{ ballot: {self.ballot!r} }}"


@dataclass(frozen=True)
class Decided:
    ballot: Tuple
    proposal: Tuple

    def __repr__(self):
        return (f"Decided {{ ballot: {self.ballot!r}, "
                f"proposal: {self.proposal!r} }}")


@dataclass(frozen=True)
class PaxosState:
    # shared state
    ballot: Tuple
    # leader state
    proposal: Optional[Tuple]
    prepares: Tuple  # sorted tuple of (acceptor_id, last_accepted)
    accepts: Tuple   # sorted tuple of acceptor ids
    # acceptor state
    accepted: Optional[Tuple]
    is_decided: bool


def _prepares_insert(prepares: Tuple, id: Id, last_accepted) -> Tuple:
    entries = dict(prepares)
    entries[id] = last_accepted
    return tuple(sorted(entries.items()))


def _accepted_key(last_accepted):
    # Option ordering: None < Some(v), then lexicographic (paxos.rs:175-177)
    return (0,) if last_accepted is None else (1, last_accepted)


class PaxosActor(Actor):
    """`paxos.rs:96-222`."""

    def __init__(self, peer_ids):
        self.peer_ids = list(peer_ids)

    def on_start(self, id: Id, o: Out) -> PaxosState:
        return PaxosState(
            ballot=(0, Id(0)),
            proposal=None,
            prepares=(),
            accepts=(),
            accepted=None,
            is_decided=False,
        )

    def on_msg(self, id: Id, state: PaxosState, src: Id, msg, o: Out):
        if state.is_decided:
            if type(msg) is Get:
                # Don't reply when undecided: a value may have been decided
                # elsewhere with delivery pending (paxos.rs:118-126).
                _b, (_req_id, _src, value) = state.accepted
                o.send(src, GetOk(msg.request_id, value))
            return None

        if type(msg) is Put and state.proposal is None:
            ballot = (state.ballot[0] + 1, id)
            o.broadcast(self.peer_ids, Internal(Prepare(ballot)))
            return replace(
                state,
                proposal=(msg.request_id, src, msg.value),
                # Simulate Prepare + Prepared self-sends.
                ballot=ballot,
                prepares=_prepares_insert((), id, state.accepted),
                accepts=(),
            )
        if type(msg) is not Internal:
            return None
        inner = msg.msg

        if type(inner) is Prepare and state.ballot < inner.ballot:
            o.send(src, Internal(Prepared(
                ballot=inner.ballot,
                last_accepted=state.accepted,
            )))
            return replace(state, ballot=inner.ballot)

        if type(inner) is Prepared and inner.ballot == state.ballot:
            prepares = _prepares_insert(
                state.prepares, src, inner.last_accepted)
            state = replace(state, prepares=prepares)
            if len(prepares) == majority(len(self.peer_ids) + 1):
                # Leadership handoff: favor the most recently accepted
                # proposal from the prepare quorum (paxos.rs:158-179).
                best = max((la for _, la in prepares), key=_accepted_key)
                proposal = (best[1] if best is not None
                            else state.proposal)
                ballot = inner.ballot
                o.broadcast(self.peer_ids,
                            Internal(Accept(ballot, proposal)))
                # Simulate Accept + Accepted self-sends.
                state = replace(
                    state,
                    proposal=proposal,
                    accepted=(ballot, proposal),
                    accepts=tuple(sorted(set(state.accepts) | {id})),
                )
            return state

        if type(inner) is Accept and state.ballot <= inner.ballot:
            o.send(src, Internal(Accepted(inner.ballot)))
            return replace(state, ballot=inner.ballot,
                           accepted=(inner.ballot, inner.proposal))

        if type(inner) is Accepted and inner.ballot == state.ballot:
            accepts = tuple(sorted(set(state.accepts) | {src}))
            state = replace(state, accepts=accepts)
            if len(accepts) == majority(len(self.peer_ids) + 1):
                proposal = state.proposal
                o.broadcast(self.peer_ids,
                            Internal(Decided(inner.ballot, proposal)))
                request_id, requester_id, _ = proposal
                o.send(requester_id, PutOk(request_id))
                state = replace(state, is_decided=True)
            return state

        if type(inner) is Decided:
            return replace(state, ballot=inner.ballot,
                           accepted=(inner.ballot, inner.proposal),
                           is_decided=True)
        return None


@dataclass
class PaxosModelCfg:
    client_count: int
    server_count: int
    #: adds the liveness property "eventually chosen" (Expectation
    #: EVENTUALLY): a counterexample is a terminal path on which no
    #: client ever observed a chosen value — reachable here because
    #: clients never retry, so dueling proposers can wedge (the classic
    #: Paxos liveness caveat; FLP). BASELINE.json config 5.
    liveness: bool = False
    #: checks under client symmetry: the model is ``symmetric``, so
    #: ``checker()`` returns a ``CheckerBuilder`` with ``.symmetry()``
    #: on and the device and native engines dedup by
    #: ``RegisterWorkloadDevice.representative`` (clients exchanged
    #: within their residue class mod the server count). BASELINE.json
    #: config 5.
    symmetry: bool = False

    def into_model(self) -> ActorModel:
        def value_chosen(_model, state):
            for env in state.network:
                if type(env.msg) is GetOk and env.msg.value != NO_VALUE:
                    return True
            return False

        model = ActorModel(
            cfg=self,
            init_history=LinearizabilityTester(Register(NO_VALUE)))
        for i in range(self.server_count):
            model.actor(RegisterActor.wrap(
                PaxosActor(model_peers(i, self.server_count))))
        for _ in range(self.client_count):
            model.actor(RegisterActor.client(
                put_count=1, server_count=self.server_count))
        model = (model
                 .with_duplicating_network(False)
                 .property(Expectation.ALWAYS, "linearizable", lambda _, s:
                           s.history.serialized_history() is not None)
                 .property(Expectation.SOMETIMES, "value chosen",
                           value_chosen)
                 .record_msg_in(record_returns)
                 .record_msg_out(record_invocations))
        if self.liveness:
            model = model.property(Expectation.EVENTUALLY,
                                   "eventually chosen", value_chosen)

        def device_model():
            from stateright_tpu.tpu.models.paxos import PaxosDevice

            return PaxosDevice(self.client_count, self.server_count,
                               sys.modules[__name__],
                               liveness=self.liveness)

        model.device_model = device_model
        model.symmetric = self.symmetry
        return model


def main(argv):
    from _check_util import parse_flags, run_check

    # An optional trailing "liveness" adds the "eventually chosen"
    # Eventually property (BASELINE.json config 5); "--python" forces
    # the pure-Python reference engine on the check arm.
    liveness = "liveness" in argv[2:]
    use_python, argv = parse_flags(argv)
    argv = [a for a in argv if a != "liveness"]
    cmd = argv[1] if len(argv) > 1 else None
    if cmd == "check":
        client_count = int(argv[2]) if len(argv) > 2 else 2
        print(f"Model checking Single Decree Paxos with {client_count} "
              "clients.")
        run_check(PaxosModelCfg(client_count, 3, liveness=liveness)
                  .into_model().checker().threads(os.cpu_count()),
                  use_python)
    elif cmd == "check-sym":
        # Client-exchangeability symmetry (driver config 5): dedup by the
        # canonical member of each client-permutation orbit. The group is
        # nontrivial only when two clients share a residue mod the server
        # count (first at 4 clients with 3 servers); see
        # RegisterWorkloadDevice.client_permutations for the derivation.
        client_count = int(argv[2]) if len(argv) > 2 else 2
        print(f"Model checking Single Decree Paxos with {client_count} "
              "clients using symmetry reduction.")
        model = PaxosModelCfg(client_count, 3,
                              liveness=liveness).into_model()
        dm = model.device_model()
        (model.checker().threads(os.cpu_count())
         .symmetry_fn(dm.host_representative)
         .spawn_dfs().join().report(sys.stdout))
    elif cmd == "check-sym-tpu":
        client_count = int(argv[2]) if len(argv) > 2 else 2
        print(f"Model checking Single Decree Paxos with {client_count} "
              "clients on the TPU engine using symmetry reduction.")
        (PaxosModelCfg(client_count, 3, liveness=liveness).into_model()
         .checker().symmetry()
         .spawn_tpu_bfs().join().report(sys.stdout))
    elif cmd == "check-sym-native":
        client_count = int(argv[2]) if len(argv) > 2 else 2
        print(f"Model checking Single Decree Paxos with {client_count} "
              "clients on the native C++ engine using symmetry reduction.")
        model = PaxosModelCfg(client_count, 3,
                              liveness=liveness).into_model()
        (model.checker().threads(os.cpu_count()).symmetry()
         .spawn_native_dfs(model.device_model()).join().report(sys.stdout))
    elif cmd == "check-tpu":
        client_count = int(argv[2]) if len(argv) > 2 else 2
        print(f"Model checking Single Decree Paxos with {client_count} "
              "clients on the TPU engine.")
        (PaxosModelCfg(client_count, 3, liveness=liveness).into_model()
         .checker()
         .spawn_tpu_bfs().join().report(sys.stdout))
    elif cmd == "check-native":
        client_count = int(argv[2]) if len(argv) > 2 else 2
        print(f"Model checking Single Decree Paxos with {client_count} "
              "clients on the native C++ engine.")
        model = PaxosModelCfg(client_count, 3,
                              liveness=liveness).into_model()
        (model.checker().threads(os.cpu_count())
         .spawn_native_bfs(model.device_model()).join().report(sys.stdout))
    elif cmd == "explore":
        client_count = int(argv[2]) if len(argv) > 2 else 2
        address = argv[3] if len(argv) > 3 else "localhost:3000"
        print(f"Exploring state space for Single Decree Paxos with "
              f"{client_count} clients on {address}.")
        (PaxosModelCfg(client_count, 3).into_model().checker()
         .threads(os.cpu_count()).serve(address))
    elif cmd == "spawn":
        from stateright_tpu.actor.spawn import spawn_json

        port = 3000
        print("  A set of servers that implement Single Decree Paxos.")
        print("  You can monitor and interact using tcpdump and netcat.")
        ids = [Id.from_addr("127.0.0.1", port + i) for i in range(3)]
        print("  Example interaction over netcat:")
        print('    echo \'{"Put": [0, "X"]}\' | nc -u 127.0.0.1 3000')
        print('    echo \'{"Get": 1}\' | nc -u 127.0.0.1 3000')
        spawn_json([
            (ids[0], PaxosActor([ids[1], ids[2]])),
            (ids[1], PaxosActor([ids[0], ids[2]])),
            (ids[2], PaxosActor([ids[0], ids[1]])),
        ], msg_types=[Prepare, Prepared, Accept, Accepted, Decided])
    else:
        print("USAGE:")
        print("  paxos.py check [CLIENT_COUNT]")
        print("  paxos.py check-sym [CLIENT_COUNT] [liveness]")
        print("  paxos.py check-sym-tpu [CLIENT_COUNT] [liveness]")
        print("  paxos.py check-sym-native [CLIENT_COUNT] [liveness]")
        print("  paxos.py check-tpu [CLIENT_COUNT] [liveness]")
        print("  paxos.py check-native [CLIENT_COUNT] [liveness]")
        print("  paxos.py explore [CLIENT_COUNT] [ADDRESS]")
        print("  paxos.py spawn")


if __name__ == "__main__":
    main(sys.argv)
