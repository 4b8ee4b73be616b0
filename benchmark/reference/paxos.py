"""Plain reference for single-decree Paxos checked for linearizability
(`examples/paxos.rs` of the upstream stateright), in plain Python.

Imports nothing of the program. The system is ``S`` Paxos servers
(actor ids ``0..S-1``) and ``C`` register clients (ids ``S..S+C-1``);
client ``i`` sends ``Put(i, 'A'+i-S)`` to server ``i mod S`` at start,
and after its ``PutOk`` a ``Get(2i)`` to server ``(i+1) mod S``. The
network is a set of envelopes ``(src, dst, msg)``; delivering one
removes it (no duplication, no loss). A delivery that leaves the actor
unchanged and sends nothing is no transition. A linearizability
history rides along: each ``Put``/``Get`` sent records an invocation
by its client, each ``PutOk``/``GetOk`` delivered records the return,
and an invocation notes, for every other client, the index of its
last completed operation (the real-time order).

Properties: always "linearizable" (a total order of the completed
operations, with any in-flight ones, exists that keeps each client's
order and the real-time order and is a valid register history),
sometimes "value chosen" (a ``GetOk`` with a value is in flight).

The search is breadth-first in queue order: a state's properties are
evaluated when it is expanded, each delivery that is a transition
generates one state, and a new state joins the queue the first time
it is seen. A finished search's answers (unique states, generated
states with the initial state counted, the depth of each property's
first hit) do not depend on the order in which a state's deliveries
are taken. The answers after a prefix of the queue do, so deliveries
are taken in the order of ``env_code``: the program's documented
32-bit envelope layout (``register_workload.build_env``, the Paxos
fields of ``models/paxos.py``), copied here as a sort key and nothing
else; a state's envelopes are delivered from the smallest code up.
"""

from __future__ import annotations

import sys

NO_VALUE = "\x00"

PROPERTIES = {"linearizable": "always", "value chosen": "sometimes"}


def _la_key(la):
    return (0,) if la is None else (1, la)


class Paxos:
    def __init__(self, client_count: int, server_count: int = 3,
                 dedup_key=None):
        """``dedup_key`` maps states to the keys a new state is told
        apart by (default: the state itself); the control passes a
        lossy one."""
        self.S, self.C = server_count, client_count
        self.key = dedup_key or (lambda s: s)
        self.majority = server_count // 2 + 1
        self._server_memo = {}
        self._lin_memo = {}

    # -- Actors --------------------------------------------------------------

    def server_msg(self, sid, st, src, msg):
        """``(new state or None, sends)`` of server ``sid``."""
        key = (sid, st, src, msg)
        hit = self._server_memo.get(key)
        if hit is None:
            hit = self._server_msg(sid, st, src, msg)
            self._server_memo[key] = hit
        return hit

    def _server_msg(self, sid, st, src, msg):
        ballot, proposal, prepares, accepts, accepted, decided = st
        peers = [p for p in range(self.S) if p != sid]
        kind = msg[0]
        if decided:
            if kind == "Get":
                return None, ((src, ("GetOk", msg[1], accepted[1][2])),)
            return None, ()
        if kind == "Put" and proposal is None:
            nb = (ballot[0] + 1, sid)
            return ((nb, (msg[1], src, msg[2]), ((sid, accepted),), (),
                     accepted, False),
                    tuple((p, ("Prepare", nb)) for p in peers))
        if kind == "Prepare" and ballot < msg[1]:
            return ((msg[1], proposal, prepares, accepts, accepted, False),
                    ((src, ("Prepared", msg[1], accepted)),))
        if kind == "Prepared" and msg[1] == ballot:
            prep = dict(prepares)
            prep[src] = msg[2]
            prepares = tuple(sorted(prep.items()))
            if len(prepares) == self.majority:
                best = max((la for _, la in prepares), key=_la_key)
                proposal = best[1] if best is not None else proposal
                accepted = (ballot, proposal)
                accepts = tuple(sorted(set(accepts) | {sid}))
                return ((ballot, proposal, prepares, accepts, accepted,
                         False),
                        tuple((p, ("Accept", ballot, proposal))
                              for p in peers))
            return (ballot, proposal, prepares, accepts, accepted,
                    False), ()
        if kind == "Accept" and ballot <= msg[1]:
            return ((msg[1], proposal, prepares, accepts,
                     (msg[1], msg[2]), False),
                    ((src, ("Accepted", msg[1])),))
        if kind == "Accepted" and msg[1] == ballot:
            accepts = tuple(sorted(set(accepts) | {src}))
            if len(accepts) == self.majority:
                sends = tuple((p, ("Decided", msg[1], proposal))
                              for p in peers)
                sends += ((proposal[1], ("PutOk", proposal[0])),)
                return ((ballot, proposal, prepares, accepts, accepted,
                         True), sends)
            return (ballot, proposal, prepares, accepts, accepted,
                    False), ()
        if kind == "Decided":
            return ((msg[1], proposal, prepares, accepts,
                     (msg[1], msg[2]), True), ())
        return None, ()

    def client_msg(self, cid, st, msg):
        awaiting, op_count = st
        if awaiting is None:
            return None, ()
        if msg[0] == "PutOk" and msg[1] == awaiting:
            rid = (op_count + 1) * cid
            dst = (cid + op_count) % self.S
            return (rid, op_count + 1), ((dst, ("Get", rid)),)
        if msg[0] == "GetOk" and msg[1] == awaiting:
            return (None, op_count + 1), ()
        return None, ()

    # -- History -------------------------------------------------------------

    @staticmethod
    def invoke(history, tid, op):
        valid, hist, flight = history
        if not valid:
            return history
        if tid in dict(flight):
            return (False, hist, flight)
        cs = tuple(sorted((t, len(h) - 1) for t, h in hist
                          if t != tid and h))
        h = dict(hist)
        h.setdefault(tid, ())
        f = dict(flight)
        f[tid] = (cs, op)
        return (True, tuple(sorted(h.items())), tuple(sorted(f.items())))

    @staticmethod
    def ret(history, tid, value):
        valid, hist, flight = history
        if not valid:
            return history
        f = dict(flight)
        if tid not in f:
            return (False, hist, flight)
        cs, op = f.pop(tid)
        h = dict(hist)
        h[tid] = h.get(tid, ()) + ((cs, op, value),)
        return (True, tuple(sorted(h.items())), tuple(sorted(f.items())))

    def linearizable(self, history) -> bool:
        hit = self._lin_memo.get(history)
        if hit is None:
            hit = _linearizable(history)
            self._lin_memo[history] = hit
        return hit

    # -- The system ----------------------------------------------------------

    def init(self):
        server = ((0, 0), None, (), (), None, False)
        history = (True, (), ())
        net = set()
        clients = []
        for i in range(self.S, self.S + self.C):
            put = ("Put", i, chr(ord("A") + i - self.S))
            net.add((i, i % self.S, put))
            history = self.invoke(history, i, ("W", put[2]))
            clients.append((i, 1))
        return ((server,) * self.S, tuple(clients), frozenset(net),
                history)

    def successors(self, state):
        servers, clients, net, history = state
        out = []
        for env in sorted(net, key=self.env_code):
            src, dst, msg = env
            if dst < self.S:
                new, sends = self.server_msg(dst, servers[dst], src, msg)
            else:
                new, sends = self.client_msg(dst, clients[dst - self.S],
                                             msg)
            if new is None and not sends:
                continue
            h = history
            if msg[0] == "GetOk":
                h = self.ret(h, dst, ("ROk", msg[2]))
            elif msg[0] == "PutOk":
                h = self.ret(h, dst, ("WOk",))
            n = set(net)
            n.discard(env)
            sv, cl = servers, clients
            if new is not None:
                if dst < self.S:
                    sv = sv[:dst] + (new,) + sv[dst + 1:]
                else:
                    k = dst - self.S
                    cl = cl[:k] + (new,) + cl[k + 1:]
            for to, m in sends:
                if m[0] == "Put":
                    h = self.invoke(h, dst, ("W", m[2]))
                elif m[0] == "Get":
                    h = self.invoke(h, dst, ("R",))
                n.add((dst, to, m))
            out.append((sv, cl, frozenset(n), h))
        return out

    def holds(self, state) -> dict:
        return {"linearizable": self.linearizable(state[3]),
                "value chosen": any(m[0] == "GetOk" and m[2] != NO_VALUE
                                    for _s, _d, m in state[2])}

    def env_code(self, env) -> int:
        """The envelope's place in a state's delivery order."""
        src, dst, msg = env
        S, C = self.S, self.C
        kinds = {"Put": 0, "Get": 1, "PutOk": 2, "GetOk": 3, "Prepare": 4,
                 "Prepared": 5, "Accept": 6, "Accepted": 7, "Decided": 8}
        kind = kinds[msg[0]]

        def value(v):
            return 0 if v == NO_VALUE else ord(v) - ord("A") + 1

        def ballot(b):
            return 0 if b[0] == 0 else 1 + (b[0] - 1) * S + b[1]

        def proposal(p):
            return 0 if p is None else p[1] - S + 1

        def la(a):
            return (0 if a is None
                    else 1 + (ballot(a[0]) - 1) * C + proposal(a[1]) - 1)

        req = val = extra = 0
        if kind <= 3:
            client = src if kind <= 1 else dst
            req = (msg[1] // client - 1) << 2 | (client - S)
            if msg[0] in ("Put", "GetOk"):
                val = value(msg[2])
        else:
            extra = ballot(msg[1])
            if msg[0] == "Prepared":
                extra |= la(msg[2]) << (6 if C <= 3 else 7)
            elif msg[0] in ("Accept", "Decided"):
                extra |= proposal(msg[2]) << 4
        shift = 15 if C <= 3 else 16
        return (dst | src << 3 | kind << 6 | req << 10 | val << 13
                | extra << shift)

    # -- Breadth-first search ------------------------------------------------

    def _start(self):
        init = self.init()
        self.queue = [init]
        self.seen = {self.key(init)}
        self.depth = [0]
        self.cum_novel = [0]   # new states found by rows [0, i)
        self.cum_gen = [0]     # states generated by rows [0, i)
        self.first_hit = {}    # property -> (queue row, depth)

    def ensure_expanded(self, rows: int) -> None:
        if not hasattr(self, "queue"):
            self._start()
        queue, seen = self.queue, self.seen
        i = len(self.cum_novel) - 1
        while i < min(rows, len(queue)):
            s = queue[i]
            for name, ok in self.holds(s).items():
                if name not in self.first_hit and ok == (
                        PROPERTIES[name] == "sometimes"):
                    self.first_hit[name] = (i, self.depth[i])
            kids = self.successors(s)
            novel = 0
            for t in kids:
                k = self.key(t)
                if k not in seen:
                    seen.add(k)
                    queue.append(t)
                    self.depth.append(self.depth[i] + 1)
                    novel += 1
            self.cum_novel.append(self.cum_novel[-1] + novel)
            self.cum_gen.append(self.cum_gen[-1] + len(kids))
            i += 1

    def prefix(self, head: int) -> dict:
        """What a search that has expanded the first ``head`` queue rows
        has found."""
        self.ensure_expanded(head)
        head = min(head, len(self.cum_novel) - 1)
        disc = {name: depth for name, (row, depth)
                in self.first_hit.items() if row < head}
        return {"head": head, "unique": 1 + self.cum_novel[head],
                "states": 1 + self.cum_gen[head], "discoveries": disc}

    def waves(self, batch: int, count: int) -> dict:
        """The prefix after ``count`` waves of at most ``batch`` rows,
        each wave taking the queue rows that were there when it began."""
        head, tail = 0, 1
        for _ in range(count):
            new_head = min(head + batch, tail)
            if new_head == head:
                break
            self.ensure_expanded(new_head)
            head, tail = new_head, 1 + self.cum_novel[new_head]
        return self.prefix(head)

    def complete(self) -> dict:
        self.ensure_expanded(float("inf"))
        return self.prefix(len(self.queue))


def _linearizable(history) -> bool:
    """Whether some order of the completed operations, with any subset
    of the in-flight ones, is a valid register history that keeps each
    client's order and every recorded real-time edge."""
    valid, hist, flight = history
    if not valid:
        return False
    ops = {t: h for t, h in hist}
    flight = dict(flight)
    threads = sorted(set(ops) | set(flight))

    def placed_before(nexts, cs):
        # every peer op at or before the recorded index is placed
        return all(nexts.get(peer, 0) > idx for peer, idx in cs)

    def search(nexts, done_flight, value):
        if all(nexts[t] == len(ops.get(t, ())) for t in threads):
            return True
        for t in threads:
            seq = ops.get(t, ())
            i = nexts[t]
            if i < len(seq):
                cs, op, ret = seq[i]
                if not placed_before(nexts, cs):
                    continue
                if op[0] == "W":
                    if ret != ("WOk",):
                        continue
                    new_value = op[1]
                else:
                    if ret != ("ROk", value):
                        continue
                    new_value = value
                if search(_bump(nexts, t), done_flight, new_value):
                    return True
            elif t in flight and t not in done_flight:
                cs, op = flight[t]
                if not placed_before(nexts, cs):
                    continue
                new_value = op[1] if op[0] == "W" else value
                if search(nexts, done_flight | {t}, new_value):
                    return True
        return False

    return search({t: 0 for t in threads}, frozenset(), NO_VALUE)


def _bump(nexts, t):
    out = dict(nexts)
    out[t] += 1
    return out


def make(params: dict, dedup_key=None) -> Paxos:
    return Paxos(int(params["client_count"]), int(params["server_count"]),
                 dedup_key=dedup_key)


if __name__ == "__main__":
    # python3 benchmark/reference/paxos.py 3  -> the complete answers
    import json
    import time

    t0 = time.monotonic()
    answers = Paxos(int(sys.argv[1]) if len(sys.argv) > 1 else 2).complete()
    print(json.dumps(dict(answers, seconds=time.monotonic() - t0)))
