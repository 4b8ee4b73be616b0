"""Plain reference for two-phase commit (`examples/2pc.rs` of the
upstream stateright, Gray & Lamport's TLA+ subset), in numpy.

Imports nothing of the program. A state is one integer of our own
layout (N = resource managers):

    bits [2i, 2i+2)      RM i: 0 working, 1 prepared, 2 committed, 3 aborted
    bits [2N, 2N+2)      TM: 0 init, 1 committed, 2 aborted
    bit  2N+2+i          TM has seen RM i prepared
    bit  3N+2            Commit message sent
    bit  3N+3            Abort message sent
    bit  3N+4+i          Prepared(i) message sent

The actions per state, in the upstream's order (2pc.rs:52-76): TmCommit,
TmAbort, then for each RM: TmRcvPrepared, RmPrepare, RmChooseToAbort,
RmRcvCommitMsg, RmRcvAbortMsg. Properties: sometimes "abort agreement",
sometimes "commit agreement", always "consistent".

Breadth-first search visits states in queue order: a state is expanded
when it leaves the queue, its properties are evaluated then, and each
valid action yields one generated state (self-loops included); a
generated state joins the queue the first time it is seen. Every
action that changes the state adds one event to its history, and the
state fixes how many events that history has (``event_count``), so a
state's depth is its event count and a new state can only be found
among the next depth's states. ``_expand_level`` checks that on every
level, so a departure shows as an error, not as a wrong count.
"""

from __future__ import annotations

import numpy as np

WORKING, PREPARED, COMMITTED, ABORTED = range(4)
TM_INIT, TM_COMMITTED, TM_ABORTED = range(3)

PROPERTIES = {"abort agreement": "sometimes",
              "commit agreement": "sometimes",
              "consistent": "always"}


class Layout:
    def __init__(self, n: int):
        if not 1 <= n <= 14:
            raise ValueError("the 64-bit layout holds 1 to 14 RMs")
        self.n = n
        self.tm = 2 * n
        self.prep = 2 * n + 2
        self.commit = 3 * n + 2
        self.abort = 3 * n + 3
        self.msg = 3 * n + 4
        self.actions = 2 + 5 * n

    def rm(self, s, i):
        return (s >> np.uint64(2 * i)) & np.uint64(3)

    def bit(self, s, b):
        return (s >> np.uint64(b)) & np.uint64(1)

    def tm_state(self, s):
        return (s >> np.uint64(self.tm)) & np.uint64(3)


def _set_field(s, shift, width, value):
    mask = np.uint64(((1 << width) - 1) << shift)
    return (s & ~mask) | (np.uint64(value) << np.uint64(shift))


def children(lay: Layout, s: np.ndarray):
    """Generated states of ``s`` (uint64[n]) as (uint64[n, A],
    bool[n, A]) in action order."""
    one = np.uint64(1)
    tm_init = lay.tm_state(s) == TM_INIT
    all_prep = np.ones(len(s), bool)
    for i in range(lay.n):
        all_prep &= lay.bit(s, lay.prep + i) == one
    has_commit = lay.bit(s, lay.commit) == one
    has_abort = lay.bit(s, lay.abort) == one
    kids = [_set_field(s, lay.tm, 2, TM_COMMITTED)
            | (one << np.uint64(lay.commit)),
            _set_field(s, lay.tm, 2, TM_ABORTED)
            | (one << np.uint64(lay.abort))]
    valid = [tm_init & all_prep, tm_init]
    for i in range(lay.n):
        rm = lay.rm(s, i)
        kids += [s | (one << np.uint64(lay.prep + i)),
                 _set_field(s, 2 * i, 2, PREPARED)
                 | (one << np.uint64(lay.msg + i)),
                 _set_field(s, 2 * i, 2, ABORTED),
                 _set_field(s, 2 * i, 2, COMMITTED),
                 _set_field(s, 2 * i, 2, ABORTED)]
        valid += [tm_init & (lay.bit(s, lay.msg + i) == one),
                  rm == WORKING, rm == WORKING, has_commit, has_abort]
    return np.stack(kids, axis=1), np.stack(valid, axis=1)


def event_count(lay: Layout, s: np.ndarray) -> np.ndarray:
    """Events in any history that reaches ``s``: one per RmPrepare,
    RmChooseToAbort, RmRcv*, TmRcvPrepared, TmCommit, TmAbort. An
    aborted RM with a Prepared message prepared and then received
    Abort (2); without one it aborted at once (1)."""
    ev = np.zeros(len(s), np.int64)
    for i in range(lay.n):
        rm = lay.rm(s, i).astype(np.int64)
        sent = lay.bit(s, lay.msg + i).astype(np.int64)
        ev += np.where(rm == PREPARED, 1, 0)
        ev += np.where(rm == COMMITTED, 2, 0)
        ev += np.where(rm == ABORTED, 1 + sent, 0)
        ev += lay.bit(s, lay.prep + i).astype(np.int64)
    ev += (lay.tm_state(s) != TM_INIT).astype(np.int64)
    return ev


def holds(lay: Layout, s: np.ndarray) -> dict:
    """Each property's condition on ``s`` (bool arrays)."""
    rms = np.stack([lay.rm(s, i) for i in range(lay.n)], axis=1)
    aborted = rms == ABORTED
    committed = rms == COMMITTED
    return {"abort agreement": aborted.all(axis=1),
            "commit agreement": committed.all(axis=1),
            "consistent": ~(aborted.any(axis=1) & committed.any(axis=1))}


class TwoPhaseReference:
    """Breadth-first search of 2pc with ``rm_count`` RMs, level by level,
    grown on demand. Queue row ``i`` (levels concatenated) adds
    ``novel[i]`` new states and generates ``generated[i]``."""

    def __init__(self, rm_count: int, chunk: int = 1 << 19,
                 dedup_key=None):
        """``dedup_key`` maps states to the keys a new state is told
        apart by (default: the state itself); the control passes a
        lossy one."""
        self.lay = Layout(rm_count)
        self.key = dedup_key or (lambda k: k)
        self.chunk = chunk
        self.levels = [np.zeros(1, np.uint64)]  # the init state
        self._novel: list = []      # per expanded level
        self._generated: list = []  # per expanded level
        self._cum = None            # (cumulative novel, generated)
        self.first_hit: dict = {}   # property -> (queue row, depth)

    # -- Search ------------------------------------------------------------

    def _expand_level(self) -> bool:
        """Expands the newest level; False once the queue is drained."""
        d = len(self._novel)
        level = self.levels[d]
        if len(level) == 0:
            return False
        lay = self.lay
        self._note_properties(level, self.expanded_rows(), d)
        novel = np.zeros(len(level), np.int64)
        generated = np.zeros(len(level), np.int64)
        seen = np.zeros(0, np.uint64)  # the next level so far, sorted
        parts = []
        for lo in range(0, len(level), self.chunk):
            par = level[lo:lo + self.chunk]
            kids, valid = children(lay, par)
            generated[lo:lo + len(par)] = valid.sum(axis=1)
            flat_idx = np.flatnonzero((valid & (kids != par[:, None]))
                                      .ravel())
            flat = kids.ravel()[flat_idx]
            if len(flat) and np.any(event_count(lay, flat) != d + 1):
                raise AssertionError(
                    "a new state off the next depth: the event-count "
                    "argument does not hold")
            keys, first = np.unique(self.key(flat), return_index=True)
            keep = ~np.isin(keys, seen, assume_unique=True)
            keys, first = keys[keep], np.sort(first[keep])
            parts.append(flat[first])
            np.add.at(novel, lo + flat_idx[first] // lay.actions, 1)
            seen = np.union1d(seen, keys)
        self._novel.append(novel)
        self._generated.append(generated)
        self.levels.append(np.concatenate(parts) if parts
                           else np.zeros(0, np.uint64))
        self._cum = None
        return True

    def _note_properties(self, level, base, depth):
        for name, ok in holds(self.lay, level).items():
            if name in self.first_hit:
                continue
            hit = np.flatnonzero(ok if PROPERTIES[name] == "sometimes"
                                 else ~ok)
            if len(hit):
                self.first_hit[name] = (base + int(hit[0]), depth)

    def expanded_rows(self) -> int:
        return sum(len(x) for x in self.levels[:len(self._novel)])

    def ensure_expanded(self, rows: int) -> None:
        """Expands levels until ``rows`` queue rows are expanded or the
        queue drains."""
        while self.expanded_rows() < rows and self._expand_level():
            pass

    def _cumulative(self):
        if self._cum is None:
            zero = np.zeros(1, np.int64)
            self._cum = (np.concatenate([zero] + self._novel).cumsum(),
                         np.concatenate([zero] + self._generated).cumsum())
        return self._cum

    # -- Answers -----------------------------------------------------------

    def prefix(self, head: int) -> dict:
        """What a search that has expanded the first ``head`` queue rows
        has found: unique states (the queue's length), generated states
        (the init state counts, as in the upstream report), and each
        property discovered, with the depth of its first hit."""
        self.ensure_expanded(head)
        novel, gen = self._cumulative()
        head = min(head, len(novel) - 1)
        disc = {name: depth for name, (row, depth)
                in self.first_hit.items() if row < head}
        return {"head": head, "unique": 1 + int(novel[head]),
                "states": 1 + int(gen[head]), "discoveries": disc}

    def waves(self, batch: int, count: int) -> dict:
        """The prefix after ``count`` waves of at most ``batch`` rows,
        each wave taking the queue rows that were there when it began."""
        head, tail = 0, 1
        for _ in range(count):
            new_head = min(head + batch, tail)
            if new_head == head:
                break
            self.ensure_expanded(new_head)
            head, tail = new_head, 1 + int(self._cumulative()[0][new_head])
        return self.prefix(head)

    def complete(self) -> dict:
        while self._expand_level():
            pass
        return self.prefix(self.expanded_rows())


def make(params: dict, dedup_key=None) -> TwoPhaseReference:
    return TwoPhaseReference(int(params["rm_count"]), dedup_key=dedup_key)
