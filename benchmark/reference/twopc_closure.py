"""Plain reference for a two-phase-commit check that needs no order: the
judge of a check whose waves were cut up across shards, in numpy.

A check on a mesh of chips pops its frontier from one queue per shard,
so what it has done after some waves is not a prefix of one
breadth-first order (``twopc.py`` judges those). What holds whatever the
order is this: every admitted state is the initial state or a child of
an expanded admitted state, no state is admitted twice, and every child
of an expanded state is admitted. ``judge`` counts each departure;
``full_space`` is the closure of the whole check, level by level.

Imports nothing of the program: states are ``twopc.py``'s integers, and
the caller maps the program's rows onto them.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference.twopc import (PROPERTIES, Layout, children,
                                       event_count, holds)

#: the parent of an initial state, or of a row whose parent was never
#: admitted: no state has all 64 bits set (a layout holds at most 60)
NO_PARENT = np.uint64(2**64 - 1)
#: the initial state: every RM working, the TM at init, no message
INIT = np.uint64(0)
CHUNK = 1 << 18


def _moves(lay: Layout, states: np.ndarray):
    """``(generated, kids)`` of ``states``: how many states each
    generates (self-loops included, as the upstream report counts) and
    its children other than itself."""
    generated = np.zeros(len(states), np.int64)
    kids = []
    for lo in range(0, len(states), CHUNK):
        par = states[lo:lo + CHUNK]
        k, valid = children(lay, par)
        generated[lo:lo + len(par)] = valid.sum(axis=1)
        kids.append(k[valid & (k != par[:, None])])
    flat = np.concatenate(kids) if kids else np.zeros(0, np.uint64)
    return generated, flat


def full_space(n: int) -> dict:
    """The whole check's unique and generated states, found level by
    level as sets: each level is the children of the last that no
    earlier level holds, and lies one event deeper (``event_count``,
    the argument ``twopc.py`` checks too)."""
    lay = Layout(n)
    seen = level = np.array([INIT])
    states = depth = 1
    while len(level):
        generated, kids = _moves(lay, level)
        states += int(generated.sum())
        level = np.setdiff1d(np.unique(kids), seen, assume_unique=True)
        if np.any(event_count(lay, level) != depth):
            raise AssertionError("a new state off the next depth: the "
                                 "event-count argument does not hold")
        seen = np.union1d(seen, level)
        depth += 1
    return {"unique": len(seen), "states": states}


def _is_child(lay: Layout, parents: np.ndarray, states: np.ndarray):
    """True where ``states[i]`` is a child of ``parents[i]``."""
    out = np.zeros(len(states), bool)
    for lo in range(0, len(states), CHUNK):
        k, valid = children(lay, parents[lo:lo + CHUNK])
        out[lo:lo + len(k)] = (valid & (k == states[lo:lo + CHUNK, None])
                               ).any(axis=1)
    return out


def _replays(lay: Layout, path: list, name: str) -> bool:
    """A discovery path: from the initial state, each step a child of
    the last, ending where ``name`` is met (holds for a sometimes
    property, fails for an always one)."""
    states = np.array(path, np.uint64)
    if not len(states) or states[0] != INIT:
        return False
    if not _is_child(lay, states[:-1], states[1:]).all():
        return False
    ok = bool(holds(lay, states[-1:])[name][0])
    return ok if PROPERTIES[name] == "sometimes" else not ok


def judge(n: int, admitted: np.ndarray, parents: np.ndarray,
          expanded: np.ndarray, unique_count: int, state_count: int,
          paths: dict) -> dict:
    """The departures of a check of 2pc with ``n`` RMs from the
    reference, each a count that is 0 when the check is right.

    ``admitted`` holds every state the check admitted and ``parents``
    each one's parent state (``NO_PARENT`` for an initial state or an
    unknown parent), ``expanded`` marks the admitted states the check
    expanded, ``unique_count`` and ``state_count`` are its reported
    counts, and ``paths`` maps each discovered property to its path of
    states.

    - ``unique_diff``: admitted states that repeat, that are neither the
      initial state nor a child of their expanded parent, and children
      of expanded states that were not admitted; plus the distance of
      ``unique_count`` from the admitted rows.
    - ``states_diff``: ``state_count`` against 1 plus the states the
      expanded states generate.
    - ``disc_diff``: discovery paths that do not replay to a state where
      their property is met, properties that an expanded state meets
      and that were not discovered, and discoveries no expanded state
      explains.
    """
    lay = Layout(n)
    admitted = np.asarray(admitted, np.uint64)
    parents = np.asarray(parents, np.uint64)
    expanded = np.asarray(expanded, bool)
    distinct = np.unique(admitted)
    repeats = len(admitted) - len(distinct)

    done = np.unique(admitted[expanded])
    root = parents == NO_PARENT
    has_parent = ~root & np.isin(parents, done, assume_unique=False)
    reached = root & (admitted == INIT)
    reached[has_parent] = _is_child(lay, parents[has_parent],
                                    admitted[has_parent])
    unreached = int((~reached).sum())

    generated, kids = _moves(lay, admitted[expanded])
    missing = np.setdiff1d(np.unique(kids), distinct, assume_unique=True)

    met = holds(lay, admitted[expanded]) if expanded.any() else {}
    triggered = {name for name, ok in met.items()
                 if (ok.any() if PROPERTIES[name] == "sometimes"
                     else (~ok).any())}
    bad_paths = sum(1 for name, path in paths.items()
                    if not _replays(lay, path, name))
    disc = bad_paths + len(triggered ^ set(paths))
    return {"unique_diff": repeats + unreached + len(missing)
            + abs(int(unique_count) - len(admitted)),
            "states_diff": abs(int(state_count)
                               - (1 + int(generated.sum()))),
            "disc_diff": disc}


class TwoPhaseClosure:
    """``judge`` at one RM count."""

    def __init__(self, rm_count: int):
        Layout(rm_count)  # refuses a count the layout cannot hold
        self.n = rm_count

    def judge(self, **check) -> dict:
        return judge(self.n, **check)


def make(params: dict) -> TwoPhaseClosure:
    return TwoPhaseClosure(int(params["rm_count"]))
