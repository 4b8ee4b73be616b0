"""The core ``Model`` abstraction and ``Property`` predicates.

Counterpart of the reference's `src/lib.rs:155-300`. A ``Model`` describes a
nondeterministic transition system: initial states, enabled actions per
state, and a (partial) transition function. Properties are named predicates
with an expectation — ``ALWAYS`` (safety; the checker hunts a
counterexample), ``SOMETIMES`` (reachability; the checker hunts an example),
or ``EVENTUALLY`` (liveness; a counterexample is a terminal path that never
satisfies the predicate — only sound on acyclic state graphs, see
`lib.rs:263-267`).

Models whose transition functions are additionally expressible as JAX
functions over an encoded fixed-width state vector can opt into the TPU
engine; see ``stateright_tpu.tpu``.
"""

from __future__ import annotations

from dataclasses import dataclass
from enum import Enum
from pprint import pformat
from typing import Any, Callable, Generic, List, Optional, Tuple, TypeVar

State = TypeVar("State")
Action = TypeVar("Action")

__all__ = ["Model", "Property", "Expectation"]


class Expectation(Enum):
    """Whether a property is always, eventually, or sometimes true (lib.rs:290-300)."""

    ALWAYS = "always"
    EVENTUALLY = "eventually"
    SOMETIMES = "sometimes"


@dataclass(frozen=True)
class Property:
    """A named predicate over (model, state) with an expectation (lib.rs:244-279)."""

    expectation: Expectation
    name: str
    condition: Callable[[Any, Any], bool]

    @staticmethod
    def always(name: str, condition: Callable[[Any, Any], bool]) -> "Property":
        """A safety invariant; the checker will try to find a counterexample."""
        return Property(Expectation.ALWAYS, name, condition)

    @staticmethod
    def eventually(name: str, condition: Callable[[Any, Any], bool]) -> "Property":
        """A liveness property; a counterexample is a terminal path never
        satisfying the condition. Only sound on acyclic paths (lib.rs:263-267)."""
        return Property(Expectation.EVENTUALLY, name, condition)

    @staticmethod
    def sometimes(name: str, condition: Callable[[Any, Any], bool]) -> "Property":
        """A reachability property; the checker will try to find an example."""
        return Property(Expectation.SOMETIMES, name, condition)


class Model(Generic[State, Action]):
    """The primary abstraction: a nondeterministic transition system
    (lib.rs:155-237). Subclass and implement ``init_states``, ``actions``,
    and ``next_state``; optionally ``properties``, ``within_boundary``, and
    the explorer formatting hooks.

    A minimal sliding-tile puzzle, in the spirit of the reference's API
    doc example (`lib.rs:40-116`):

    >>> from stateright_tpu import Model, Property
    >>> class Puzzle(Model):
    ...     '''Slide the blank (0) until the board reads (0, 1, 2).'''
    ...     def init_states(self):
    ...         return [(1, 2, 0)]
    ...     def actions(self, state, actions):
    ...         actions += ["slide left", "slide right"]
    ...     def next_state(self, s, a):
    ...         b = s.index(0)
    ...         j = b - 1 if a == "slide left" else b + 1
    ...         if not 0 <= j < len(s):
    ...             return None  # the action is ignored at the edge
    ...         t = list(s)
    ...         t[b], t[j] = t[j], t[b]
    ...         return tuple(t)
    ...     def properties(self):
    ...         return [Property.sometimes(
    ...             "solved", lambda model, s: s == (0, 1, 2))]
    >>> checker = Puzzle().checker().spawn_bfs().join()
    >>> checker.assert_properties()
    >>> checker.discovery("solved").into_actions()  # shortest (BFS)
    ['slide left', 'slide left']
    >>> checker.unique_state_count()
    3
    """

    #: Whether the model is checked under symmetry reduction: its states
    #: implement ``representative()``, and ``checker()`` returns a
    #: builder with ``.symmetry()`` on. Engines that build from the
    #: model itself read it too (the job service keeps such a model out
    #: of a multiplexed group, which has no symmetry path).
    symmetric: bool = False

    def init_states(self) -> List[State]:
        """Returns the initial possible states."""
        raise NotImplementedError

    def actions(self, state: State, actions: List[Action]) -> None:
        """Appends the enabled actions for ``state`` to ``actions``."""
        raise NotImplementedError

    def next_state(self, last_state: State, action: Action) -> Optional[State]:
        """Applies ``action``; ``None`` indicates the action is ignored."""
        raise NotImplementedError

    def properties(self) -> List[Property]:
        """The expected properties of this model."""
        return []

    def within_boundary(self, state: State) -> bool:
        """Whether ``state`` is inside the state space to be checked (pruning)."""
        return True

    # -- Explorer / formatting hooks -------------------------------------

    def format_action(self, action: Action) -> str:
        return _fmt(action)

    def format_step(self, last_state: State, action: Action) -> Optional[str]:
        next_state = self.next_state(last_state, action)
        return None if next_state is None else pformat(next_state)

    def as_svg(self, path) -> Optional[str]:
        """Returns an SVG rendering of a path, if the model supports one."""
        return None

    # -- Derived helpers (lib.rs:191-225) --------------------------------

    def next_steps(self, last_state: State) -> List[Tuple[Action, State]]:
        """The (action, state) pairs that follow a particular state."""
        actions: List[Action] = []
        self.actions(last_state, actions)
        steps = []
        for action in actions:
            next_state = self.next_state(last_state, action)
            if next_state is not None:
                steps.append((action, next_state))
        return steps

    def next_states(self, last_state: State) -> List[State]:
        """The states that follow a particular state."""
        actions: List[Action] = []
        self.actions(last_state, actions)
        states = []
        for action in actions:
            next_state = self.next_state(last_state, action)
            if next_state is not None:
                states.append(next_state)
        return states

    def property(self, name: str) -> Property:
        """Looks up a property by name; raises if absent (lib.rs:218-225)."""
        for p in self.properties():
            if p.name == name:
                return p
        available = [p.name for p in self.properties()]
        raise KeyError(f"Unknown property. requested={name}, available={available}")

    def checker(self) -> "CheckerBuilder":
        """Instantiates a ``CheckerBuilder`` for this model."""
        from .checker.builder import CheckerBuilder

        return CheckerBuilder(self)


def _fmt(value: Any) -> str:
    """Debug-style formatting: Enum members print as their bare name."""
    if isinstance(value, Enum):
        return value.name
    return repr(value)
