"""``DeviceModel``: the contract a model satisfies to run on the TPU engine.

The reference accepts arbitrary Rust closures as transition functions
(`lib.rs:155-237`); XLA cannot. A model opts into the TPU engine by
supplying a *device form*: a fixed-width ``uint32`` encoding of its states
plus a jittable successor function with a static maximum fan-out and a
validity mask (the device analog of actions returning ``None`` /
``within_boundary`` pruning). The host ``Model`` remains the source of
truth for path reconstruction, formatting, and the explorer; the engine
checks that both agree via the shared encoding.

Conventions:

- A state is ``uint32[state_width]``; the encoding must be *injective*
  (distinct states -> distinct vectors), since device identity is a hash of
  the vector.
- ``step(vec) -> (succ, valid)`` with ``succ: uint32[max_fanout,
  state_width]`` and ``valid: bool[max_fanout]``. Row ``i`` corresponds to
  the i-th action in the *same order the host model enumerates actions*, so
  device BFS visits states in the same level order as the host BFS — this
  is what makes the exact state-count/discovery parity gates of
  BASELINE.md reproducible on device. Invalid rows may contain garbage.
- ``device_properties()`` maps property names (matching
  ``Model.properties()``) to jittable predicates ``uint32[W] -> bool``.
  Properties without a device predicate fall back to host evaluation on
  decoded states (correct but slow; the engine warns once).
"""

from __future__ import annotations

from typing import Callable, Dict, Optional

import numpy as np

__all__ = ["DeviceModel", "DeviceFormUnavailable"]


class DeviceFormUnavailable(NotImplementedError):
    """This model configuration exceeds what the device encoding can
    express (e.g. a register workload beyond the statically enumerated
    client bound). ``spawn_tpu_bfs`` catches this and falls back to the
    host BFS engine with a warning, so CLI/bench configurations above
    the device caps still run end to end."""


class DeviceModel:
    """The device form of a :class:`~stateright_tpu.model.Model`."""

    #: number of uint32 lanes per encoded state
    state_width: int
    #: static maximum number of actions per state
    max_fanout: int
    #: lane index that must stay 0; a nonzero value in any generated state
    #: makes the engine raise (used for encoding-capacity overflows, e.g.
    #: a bounded network exceeding its slots). None disables the check.
    error_lane: Optional[int] = None

    # -- Host-side codec -------------------------------------------------

    def encode(self, state) -> np.ndarray:
        """Encodes a host state as ``uint32[state_width]`` (injective)."""
        raise NotImplementedError

    def decode(self, vec: np.ndarray):
        """Decodes an encoded state back to the host representation."""
        raise NotImplementedError

    # -- Device-side (jittable, per single state vector) -----------------

    def step(self, vec):
        """``uint32[W] -> (uint32[max_fanout, W], bool[max_fanout])``.

        Successor states for every potential action plus a validity mask.
        Must be a pure JAX function (it is ``vmap``-ed over the frontier
        and compiled once per frontier shape).
        """
        raise NotImplementedError

    def device_properties(self) -> Dict[str, Callable]:
        """Jittable predicates ``uint32[W] -> bool`` keyed by property name."""
        return {}

    def lane_bits(self):
        """Per-lane bit widths of the encoding, for the packed storage
        row format (``tpu/packing.py``): a sequence of ``state_width``
        specs, each an int ``b`` (values fit ``b`` bits) or a
        ``(b, sentinel)`` pair for lanes with one out-of-band sentinel
        value (e.g. an actor network slot's ``EMPTY_ENV``). The declared
        widths are part of the encoding contract, like injectivity: a
        value beyond its lane's width would be silently truncated in
        the packed arena. ``None`` (the conservative default) means 32
        bits per lane — the engines then store rows unpacked."""
        return None

    def boundary(self, vec) -> Optional[object]:
        """``uint32[W] -> bool``: device analog of ``within_boundary``.

        Return ``None`` (the default, checked at trace time) when every
        successor produced by ``step`` is already within the boundary.
        """
        return None

    def representative(self, vec):
        """``uint32[W] -> uint32[W]``: canonical member of the state's
        symmetry equivalence class (device analog of `representative.rs:65`).

        Used for visited-set dedup only when the builder enables symmetry;
        paths keep original-state fingerprints (the `dfs.rs:258-267` rule).
        Default: identity-free ``None`` meaning symmetry is unsupported.
        """
        return None

    def native_form(self):
        """``(model_id, cfg)`` of this model's compiled C++ counterpart in
        ``native/host_bfs.cc``, or ``None`` (the default) when the model
        has no native form. The native model must use this exact encoding
        (it is differentially tested against ``step``), which lets
        ``spawn_native_bfs`` share fingerprints with the device engines.
        """
        return None
