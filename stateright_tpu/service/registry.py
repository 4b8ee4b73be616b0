"""``ModelRegistry``: the checking service's protocol corpus.

The service checks models *by name*: a job names a corpus entry plus
parameters, and the registry builds the host model (with its device
form attached where one exists) and produces the **canonical parameter
key** that scopes cross-job compiled-program sharing — two jobs may
share wave programs exactly when their ``(name, canonical params)``
agree, because the registry guarantees that key builds a semantically
identical model every time (``jit_cache.WaveProgramCache``'s safety
condition).

The default corpus names the repo's eight existing models — the raw
models (2pc, increment, increment-lock, sliding-puzzle) and the actor
systems (paxos, ABD, single-copy, ping-pong) — plus the round-14
addition: ``vsr``, a viewstamped-replication-style primary/backup
protocol with view change (``actor/viewstamped.py``), the corpus's
actor-path workout. Every entry is expected to pass the differential
fuzz gate (``service/diff.py``) — the cheap cross-validation every
future corpus addition runs through before it is servable.

Example model modules live under ``examples/`` as plain scripts (not a
package), so the registry extends ``sys.path`` the same way the test
suite does.
"""

from __future__ import annotations

import os
import sys
import threading
from dataclasses import dataclass
from typing import Callable, Dict, Optional, Tuple

__all__ = ["CorpusEntry", "ModelRegistry", "default_registry"]

_EXAMPLES_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "examples")


def _examples():
    """Makes the ``examples/`` scripts importable (idempotent)."""
    if _EXAMPLES_DIR not in sys.path:
        sys.path.insert(0, _EXAMPLES_DIR)


@dataclass(frozen=True)
class CorpusEntry:
    """One registered model: ``build(**params)`` returns a host model
    ready for ``checker()`` (device form attached where available);
    ``defaults`` double as the parameter schema — unknown keys are
    rejected and values are coerced to the default's type."""
    name: str
    build: Callable
    defaults: Dict[str, object]
    doc: str


class ModelRegistry:
    def __init__(self):
        self._entries: Dict[str, CorpusEntry] = {}
        self._lock = threading.Lock()

    def register(self, name: str, build: Callable,
                 defaults: Optional[Dict[str, object]] = None,
                 doc: str = "") -> None:
        with self._lock:
            if name in self._entries:
                raise ValueError(f"model {name!r} already registered")
            self._entries[name] = CorpusEntry(
                name, build, dict(defaults or {}), doc)

    def names(self):
        with self._lock:
            return sorted(self._entries)

    def entry(self, name: str) -> CorpusEntry:
        with self._lock:
            entry = self._entries.get(name)
        if entry is None:
            raise KeyError(
                f"unknown model {name!r}; registered: {self.names()}")
        return entry

    def describe(self):
        """The corpus listing (``GET /.corpus``): name, docstring, and
        the parameter schema with defaults."""
        return [{"name": e.name, "doc": e.doc, "params": dict(e.defaults)}
                for _, e in sorted(self._entries.items())]

    def resolve_params(self, name: str,
                       params: Optional[dict]) -> Dict[str, object]:
        """Defaults merged with ``params``; unknown keys rejected,
        values coerced to the default's type (an HTTP submission
        arrives as JSON — "3" and 3.0 both mean the int 3)."""
        entry = self.entry(name)
        resolved = dict(entry.defaults)
        for key, value in (params or {}).items():
            if key not in resolved:
                raise ValueError(
                    f"model {name!r} has no parameter {key!r}; "
                    f"accepts {sorted(resolved)}")
            want = type(resolved[key])
            try:
                resolved[key] = (bool(value) if want is bool
                                 else want(value))
            except (TypeError, ValueError) as e:
                raise ValueError(
                    f"parameter {key!r} of model {name!r}: {e}") from e
        return resolved

    def build(self, name: str, params: Optional[dict] = None):
        """Builds the host model; returns ``(model, canonical_params)``."""
        entry = self.entry(name)
        resolved = self.resolve_params(name, params)
        return entry.build(**resolved), resolved

    def program_key(self, name: str, params: Optional[dict] = None
                    ) -> Tuple:
        """The shared-program-cache key prefix certifying model
        identity: the corpus name plus the canonical parameter items."""
        resolved = self.resolve_params(name, params)
        return (name, tuple(sorted(resolved.items())))


# -- The default corpus ----------------------------------------------------


def _twopc(rm_count):
    _examples()
    from two_phase_commit import TwoPhaseSys

    return TwoPhaseSys(rm_count)


def _paxos(client_count, server_count, liveness, symmetry):
    _examples()
    from paxos import PaxosModelCfg

    return PaxosModelCfg(client_count=client_count,
                         server_count=server_count, liveness=liveness,
                         symmetry=symmetry).into_model()


def _increment(thread_count):
    _examples()
    from increment import IncrementModel

    return IncrementModel(thread_count)


def _increment_lock(thread_count):
    _examples()
    from increment_lock import IncrementLockModel

    return IncrementLockModel(thread_count)


def _single_copy(client_count, server_count):
    _examples()
    from single_copy_register import SingleCopyModelCfg

    return SingleCopyModelCfg(client_count=client_count,
                              server_count=server_count).into_model()


def _abd(client_count, server_count):
    _examples()
    from linearizable_register import AbdModelCfg

    return AbdModelCfg(client_count=client_count,
                       server_count=server_count).into_model()


def _pingpong(max_nat, maintains_history, lossy, duplicating):
    from ..actor.actor_test_util import PingPongCfg

    cfg = PingPongCfg(maintains_history=maintains_history,
                      max_nat=max_nat)
    model = (cfg.into_model()
             .with_lossy_network(lossy)
             .with_duplicating_network(duplicating))

    def device_model():
        import stateright_tpu.actor.actor_test_util as ppmod

        from ..tpu.models.pingpong import PingPongDevice

        return PingPongDevice(cfg, ppmod, lossy=lossy,
                              duplicating=duplicating)

    model.device_model = device_model
    return model


def _sliding_puzzle(rows, cols):
    _examples()
    from sliding_puzzle import SlidingPuzzle

    return SlidingPuzzle(rows, cols)


def _vsr(n, max_view, lossy, duplicating):
    from ..actor.viewstamped import VsrCfg

    return VsrCfg(n=n, max_view=max_view, lossy=lossy,
                  duplicating=duplicating).into_model()


_DEFAULT: Optional[ModelRegistry] = None
_DEFAULT_LOCK = threading.Lock()


def default_registry() -> ModelRegistry:
    """The process-wide default corpus (built once)."""
    global _DEFAULT
    with _DEFAULT_LOCK:
        if _DEFAULT is not None:
            return _DEFAULT
        r = ModelRegistry()
        r.register("twopc", _twopc, {"rm_count": 3},
                   "two-phase commit (Gray & Lamport TLA+ subset)")
        r.register("paxos", _paxos,
                   {"client_count": 2, "server_count": 3,
                    "liveness": False, "symmetry": False},
                   "single-decree Paxos with linearizability history "
                   "(liveness: adds \"eventually chosen\"; symmetry: "
                   "clients exchanged within their residue class)")
        r.register("increment", _increment, {"thread_count": 3},
                   "racy read-inc-write counter (finds the lost update)")
        r.register("increment_lock", _increment_lock,
                   {"thread_count": 3},
                   "spinlock-guarded counter (race eliminated)")
        r.register("single_copy", _single_copy,
                   {"client_count": 2, "server_count": 1},
                   "single-copy register (linearizable by construction)")
        r.register("abd", _abd, {"client_count": 2, "server_count": 2},
                   "ABD quorum register (linearizable reads/writes)")
        r.register("pingpong", _pingpong,
                   {"max_nat": 3, "maintains_history": False,
                    "lossy": False, "duplicating": True},
                   "ping-pong counter pair (actor-layer workout)")
        r.register("sliding_puzzle", _sliding_puzzle,
                   {"rows": 2, "cols": 3},
                   "sliding tile puzzle (search workload)")
        r.register("vsr", _vsr,
                   {"n": 3, "max_view": 1, "lossy": False,
                    "duplicating": True},
                   "viewstamped-replication primary/backup with view "
                   "change (round-14 corpus addition)")
        _DEFAULT = r
        return r
