"""Plain reference for single-decree Paxos under client symmetry, with
the liveness property "eventually chosen" (BASELINE.json config 5:
`examples/paxos.rs` `check 4`, plus symmetry and the eventually
property), in plain Python.

Builds on ``reference/paxos.py`` (its actors, history, delivery order
and search) through its ``dedup_key`` hook, and imports nothing of the
program.

**Client symmetry.** Client ``k`` (actor id ``S+k``) sends its ``Put``
to server ``k mod S`` and its ``Get`` to server ``(k+1) mod S``, so
two clients are interchangeable exactly where their indices agree mod
``S``. The group is every permutation of the client indices that keeps
each index within its residue class; at 3 servers it is trivial below 4
clients and {id, swap(client 0, client 3)} at 4. A permutation acts on
a state by moving each client's own state to its new index and
rewriting every payload derived from a client: actor ids in envelopes
and proposals, values (``'A'+k``), request ids (``op * id``), the
history's thread keys and the real-time "last completed op" indices it
records. A new state is told apart from those seen by its orbit (the
set of its images under the group), so states in one orbit count once;
the state kept and expanded is the first one seen.

**The eventually rule** (stateright's `bfs.rs`). Every initial state
carries the property's bit. When a state is expanded its bit is
cleared if "eventually chosen" holds there; each new state inherits
the bit of the state that first reached it. A terminal state (one with
no transition) whose bit is still set is a discovery at its depth.
"""

from __future__ import annotations

import itertools
import os
import sys

try:
    from benchmark.reference.paxos import NO_VALUE, Paxos
except ImportError:  # run as a script
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    from benchmark.reference.paxos import NO_VALUE, Paxos

EVENTUALLY = "eventually chosen"


def client_permutations(client_count: int, server_count: int) -> list:
    """Every permutation ``sigma`` (old client index -> new) that keeps
    each client within its residue class mod ``server_count``, the
    identity first."""
    classes = {}
    for k in range(client_count):
        classes.setdefault(k % server_count, []).append(k)
    out = []
    for combo in itertools.product(*(itertools.permutations(m)
                                     for m in classes.values())):
        sigma = [0] * client_count
        for members, image in zip(classes.values(), combo):
            for old, new in zip(members, image):
                sigma[old] = new
        out.append(tuple(sigma))
    return sorted(out, key=lambda s: s != tuple(range(client_count)))


class ClientMap:
    """One client permutation acting on the reference's states."""

    def __init__(self, sigma: tuple, server_count: int):
        self.S = server_count
        self.ids = {server_count + k: server_count + new
                    for k, new in enumerate(sigma)}
        self.inv = [sigma.index(j) for j in range(len(sigma))]
        self._parts = [({}, fn) for fn in (self._servers, self.clients,
                                           self._net, self.history)]
        self._envelopes = {}

    def actor(self, a):
        return self.ids.get(a, a)

    def value(self, v):
        if v == NO_VALUE:
            return v
        return chr(ord("A") + self.actor(ord(v) - ord("A") + self.S)
                   - self.S)

    def rid(self, rid, client):
        return rid // client * self.actor(client)

    def proposal(self, p):
        if p is None:
            return None
        rid, client, value = p
        return (self.rid(rid, client), self.actor(client), self.value(value))

    def accepted(self, la):
        return None if la is None else (la[0], self.proposal(la[1]))

    def msg(self, src, dst, msg):
        kind = msg[0]
        if kind in ("Put", "Get"):
            return (kind, self.rid(msg[1], src)) + tuple(
                self.value(v) for v in msg[2:])
        if kind in ("PutOk", "GetOk"):
            return (kind, self.rid(msg[1], dst)) + tuple(
                self.value(v) for v in msg[2:])
        if kind == "Prepared":
            return (kind, msg[1], self.accepted(msg[2]))
        if kind in ("Accept", "Decided"):
            return (kind, msg[1], self.proposal(msg[2]))
        return msg  # Prepare, Accepted: ballots only

    def envelope(self, env):
        src, dst, msg = env
        return (self.actor(src), self.actor(dst), self.msg(src, dst, msg))

    def server(self, st):
        ballot, proposal, prepares, accepts, accepted, decided = st
        return (ballot, self.proposal(proposal),
                tuple((sid, self.accepted(la)) for sid, la in prepares),
                accepts, self.accepted(accepted), decided)

    def clients(self, clients):
        out = []
        for j in range(len(clients)):
            k = self.inv[j]  # the client that moves to index j
            awaiting, op_count = clients[k]
            if awaiting is not None:
                awaiting = self.rid(awaiting, self.S + k)
            out.append((awaiting, op_count))
        return tuple(out)

    def _peers(self, cs):
        return tuple(sorted((self.actor(t), i) for t, i in cs))

    def _op(self, op):
        return (op[0],) + tuple(self.value(v) for v in op[1:])

    def history(self, history):
        valid, hist, flight = history
        hist = tuple(sorted(
            (self.actor(t), tuple((self._peers(cs), self._op(op),
                                   self._op(ret)) for cs, op, ret in ops))
            for t, ops in hist))
        flight = tuple(sorted(
            (self.actor(t), (self._peers(cs), self._op(op)))
            for t, (cs, op) in flight))
        return (valid, hist, flight)

    def _servers(self, servers):
        return tuple(self.server(st) for st in servers)

    def _net(self, net):
        memo = self._envelopes
        out = []
        for e in net:
            image = memo.get(e)
            if image is None:
                image = memo[e] = self.envelope(e)
            out.append(image)
        return frozenset(out)

    def state(self, state):
        """The image of ``state``; each of its four parts is memoized
        (they recur across many states)."""
        out = []
        for part, (memo, fn) in zip(state, self._parts):
            image = memo.get(part)
            if image is None:
                image = memo[part] = fn(part)
            out.append(image)
        return tuple(out)


class PaxosSym(Paxos):
    """``Paxos`` with the client-symmetry orbit as its dedup key and,
    with ``liveness``, the eventually property."""

    def __init__(self, client_count: int, server_count: int = 3,
                 liveness: bool = False, symmetry: bool = False):
        self.maps = ([ClientMap(s, server_count) for s in
                      client_permutations(client_count, server_count)[1:]]
                     if symmetry else [])
        super().__init__(client_count, server_count,
                         dedup_key=self.orbit if self.maps else None)
        self.liveness = liveness
        self.kinds = {"linearizable": "always",
                      "value chosen": "sometimes"}
        if liveness:
            self.kinds[EVENTUALLY] = "eventually"

    def orbit(self, state):
        """The state's orbit: the set of its images under the group."""
        return frozenset([state] + [m.state(state) for m in self.maps])

    def holds(self, state) -> dict:
        out = super().holds(state)
        if self.liveness:
            out[EVENTUALLY] = out["value chosen"]
        return out

    def _start(self):
        super()._start()
        self.ebits = [self.liveness]  # the queue rows' eventually bits

    def ensure_expanded(self, rows: int) -> None:
        if not hasattr(self, "queue"):
            self._start()
        queue, seen, first_hit = self.queue, self.seen, self.first_hit
        i = len(self.cum_novel) - 1
        while i < min(rows, len(queue)):
            s = queue[i]
            holds = self.holds(s)
            for name, kind in self.kinds.items():
                if (kind != "eventually" and name not in first_hit
                        and holds[name] == (kind == "sometimes")):
                    first_hit[name] = (i, self.depth[i])
            bit = self.ebits[i] and not holds.get(EVENTUALLY, True)
            kids = self.successors(s)
            if bit and not kids and EVENTUALLY not in first_hit:
                first_hit[EVENTUALLY] = (i, self.depth[i])
            novel = 0
            for t in kids:
                k = self.key(t)
                if k not in seen:
                    seen.add(k)
                    queue.append(t)
                    self.depth.append(self.depth[i] + 1)
                    self.ebits.append(bit)
                    novel += 1
            self.cum_novel.append(self.cum_novel[-1] + novel)
            self.cum_gen.append(self.cum_gen[-1] + len(kids))
            i += 1


def make(params: dict) -> PaxosSym:
    return PaxosSym(int(params["client_count"]),
                    int(params["server_count"]),
                    liveness=bool(params.get("liveness", False)),
                    symmetry=bool(params.get("symmetry", False)))


if __name__ == "__main__":
    # python3 benchmark/reference/paxos_sym.py 4  -> config 5's answers
    import json
    import time

    t0 = time.monotonic()
    ref = make({"client_count": int(sys.argv[1]) if len(sys.argv) > 1
                else 2, "server_count": 3, "liveness": True,
                "symmetry": True})
    print(json.dumps(dict(ref.complete(), seconds=time.monotonic() - t0)))
