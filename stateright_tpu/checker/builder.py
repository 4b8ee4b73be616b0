"""``CheckerBuilder``: configures and spawns checker engines.

Counterpart of the reference's `src/checker.rs:35-178`, plus the TPU-native
``spawn_tpu_bfs`` strategy (the BASELINE.json north star): whole-frontier
waves of vmapped successor generation with a device-resident visited set.
"""

from __future__ import annotations

from typing import Callable, Optional

from .base import Checker

__all__ = ["CheckerBuilder"]


def _pop_fused_kwargs(kwargs) -> None:
    """Strips the fused-engine-only knobs before a classic-engine
    fallback (one place: adding a fused knob must not require editing
    every fallback branch)."""
    for key in ("waves_per_dispatch", "arena_capacity",
                "inflight_dispatches"):
        kwargs.pop(key, None)



class CheckerBuilder:
    """Builds a checker for a model. Instantiate via ``model.checker()``."""

    def __init__(self, model):
        self._model = model
        self._symmetry: Optional[Callable] = None
        self._target_state_count: Optional[int] = None
        self._thread_count = 1
        self._visitor = None
        if getattr(model, "symmetric", False):
            self.symmetry()

    def spawn_bfs(self) -> Checker:
        """Spawns a breadth-first checker: more memory than DFS but finds
        the shortest path to each discovery when single-threaded (the
        default). Does not block; call ``join()``."""
        from .bfs import BfsChecker

        return BfsChecker(self)

    def spawn_dfs(self) -> Checker:
        """Spawns a depth-first checker: dramatically less memory than BFS
        at the cost of not finding shortest paths. Does not block; call
        ``join()``."""
        from .dfs import DfsChecker

        return DfsChecker(self)

    def spawn_fastest(self, device_model=None, python: bool = False
                      ) -> Checker:
        """The default ``check`` path: the compiled C++ engine when the
        model has a native form, else the Python DFS.

        In the reference, ``check`` IS the fast path (compiled, all
        cores, `examples/paxos.rs:325-331`); routing it to the
        interpreted engine would hand a user a ~300x slower default for
        no reason. ``python=True`` (the examples' ``--python`` flag)
        forces the pure-Python reference-semantics engine. With
        symmetry enabled the native DFS is used (the native BFS has no
        symmetry support); a custom ``symmetry_fn`` or a missing
        compiled representative falls back to Python, which honors
        both. Counts and property verdicts are engine-independent (the
        cross-engine parity gates in tests/); pick an explicit spawn
        when you need a specific traversal order or path shape."""
        if not python:
            try:
                if device_model is None:
                    factory = getattr(self._model, "device_model", None)
                    if factory is not None:
                        device_model = factory()
                if (device_model is not None
                        and device_model.native_form() is not None):
                    if self._symmetry is not None:
                        return self.spawn_native_dfs(device_model)
                    return self.spawn_native_bfs(device_model)
            except (NotImplementedError, ImportError, ValueError):
                # No device form for this configuration, a jax-free
                # install (resolving the device model imports
                # stateright_tpu.tpu), no native extension, no compiled
                # representative, or a native cfg rejection: the Python
                # DFS handles all of those (and honors custom
                # symmetry_fn canonicalizers).
                pass
        return self.spawn_dfs()

    def spawn_tpu_bfs(self, mesh=None, sharded=None, fused=None,
                      **kwargs) -> Checker:
        """Spawns the TPU engine: breadth-first frontier waves executed on
        device (vmapped successor generation + device hash-table dedup).
        Requires the model to provide a ``DeviceModel`` encoding; see
        ``stateright_tpu.tpu``.

        By default the *fused* engine runs: the frontier queue, visited
        table, and parent log stay device-resident and several waves run
        per dispatch (``stateright_tpu.tpu.fused``). Models that need a
        per-wave host hook (a visitor, or a property without a device
        predicate) automatically fall back to the classic per-wave
        engine; ``fused=True`` makes that fallback an error,
        ``fused=False`` forces the classic engine.

        With ``mesh=`` (or ``sharded=True``, meshing all visible devices)
        the fingerprint space is hash-partitioned across devices and each
        wave's successors are routed to their owner shard by an ICI
        all-to-all; see ``stateright_tpu.tpu.sharded``.

        Successor-path knobs (both default on; results are bit-identical
        either way — they are performance schedules, not semantics):
        ``succ_ladder=False`` disables the classic engines' K-bounded
        output compaction (waves then always gather/emit the full B*F
        successor window); ``exchange_novel_only=False`` (sharded
        engines) disables sender-side local dedup before the all-to-all
        (every valid successor then rides the interconnect, duplicates
        included).

        ``pack_arena`` (round 9, also bit-identical either way) stores
        arena/frontier rows — and the sharded engines' all-to-all
        payloads — in the model-derived bit-packed row format
        (``tpu/packing.py``). Default: packed on accelerators, unpacked
        on the CPU backend (where the codec is pure compute overhead);
        ``True``/``False`` force either arm."""
        try:
            # Enables x64 before engine import.
            import stateright_tpu.tpu as tpu
        except ImportError as e:
            import importlib.util

            if importlib.util.find_spec("jax") is not None:
                # jax exists, so this is a real error from the engine
                # package (e.g. the deliberate JAX_ENABLE_X64 opt-out
                # guard) — don't mask it.
                raise
            raise NotImplementedError(
                "the TPU engine module is not available in this build "
                "(jax is required)") from e

        if fused and kwargs.get("pipeline"):
            # pipeline= is a classic-engine knob; silently dropping an
            # explicit fused=True would violate the "fused=True makes
            # fallback an error" contract.
            raise ValueError(
                "fused=True and pipeline=True are mutually exclusive: "
                "pipelining is a classic-engine knob")
        if kwargs.get("device_model") is None:
            # Resolve the model's device form eagerly: configurations the
            # encoding cannot express (e.g. a register workload beyond
            # the device client bound) degrade to the host engine with a
            # warning instead of dying (`check-tpu` stays usable at any
            # CLI count).
            import warnings

            from ..tpu.device_model import DeviceFormUnavailable

            factory = getattr(self._model, "device_model", None)
            if factory is not None:
                try:
                    kwargs["device_model"] = factory()
                except DeviceFormUnavailable as e:
                    # The host BFS has no engine knobs: silently dropping
                    # resume_from/checkpoint_path would restart a long
                    # run from scratch AND stop writing snapshots, and an
                    # explicit fused=True promises fallback-is-an-error.
                    critical = [k for k in ("resume_from",
                                            "checkpoint_path")
                                if kwargs.get(k) is not None]
                    if fused:
                        critical.append("fused=True")
                    if critical:
                        raise DeviceFormUnavailable(
                            f"{e}; refusing the host-BFS fallback "
                            f"because it cannot honor {critical} — "
                            "drop those knobs or use a device-formable "
                            "configuration") from e
                    dropped = sorted(
                        k for k, v in kwargs.items()
                        if v is not None and k != "device_model")
                    if mesh is not None or sharded:
                        dropped.append("mesh/sharded")
                    warnings.warn(
                        f"no device form for this configuration ({e}); "
                        "falling back to the host BFS engine"
                        + (f" (dropping engine knobs {dropped})"
                           if dropped else ""),
                        RuntimeWarning)
                    return self.spawn_bfs()
        if mesh is not None or sharded:
            from ..tpu.sharded import ShardedTpuBfsChecker

            if fused is False or kwargs.get("pipeline"):
                _pop_fused_kwargs(kwargs)
                return ShardedTpuBfsChecker(self, mesh=mesh, **kwargs)
            from ..tpu.fused import FusedUnsupported
            from ..tpu.sharded_fused import ShardedFusedTpuBfsChecker

            try:
                return ShardedFusedTpuBfsChecker(self, mesh=mesh, **kwargs)
            except FusedUnsupported:
                if fused:
                    raise
                _pop_fused_kwargs(kwargs)
                return ShardedTpuBfsChecker(self, mesh=mesh, **kwargs)
        if fused is False or kwargs.get("pipeline"):
            # An explicit pipeline=True is a classic-engine opt-in.
            _pop_fused_kwargs(kwargs)
            return tpu.TpuBfsChecker(self, **kwargs)
        from ..tpu.fused import FusedTpuBfsChecker, FusedUnsupported

        try:
            return FusedTpuBfsChecker(self, **kwargs)
        except FusedUnsupported:
            if fused:
                raise
            _pop_fused_kwargs(kwargs)
            return tpu.TpuBfsChecker(self, **kwargs)

    def spawn_native_bfs(self, device_model, threads=None,
                         resume_from=None, async_io=None) -> Checker:
        """Spawns the compiled multithreaded host BFS (C++,
        ``native/host_bfs.cc``) — the reference's `bfs.rs:17-342` engine
        design operating on the model's device encoding. Requires the
        device model to declare a ``native_form()``; raises
        ``NotImplementedError`` otherwise (fall back to ``spawn_bfs``).
        ``threads`` defaults to the builder's ``threads()`` knob;
        ``resume_from`` accepts a checkpoint from any BFS engine."""
        from .native_bfs import NativeBfsChecker

        return NativeBfsChecker(self, device_model, threads=threads,
                                resume_from=resume_from,
                                async_io=async_io)

    def spawn_native_dfs(self, device_model, threads=None) -> Checker:
        """Spawns the compiled depth-first engine (C++,
        ``native/host_bfs.cc`` — the `dfs.rs:16-482` design): LIFO
        stacks, full-trace discoveries, symmetry via the model's
        compiled ``representative``. Same ``native_form()`` opt-in as
        ``spawn_native_bfs``."""
        from .native_bfs import NativeDfsChecker

        return NativeDfsChecker(self, device_model, threads=threads)

    def serve(self, addresses) -> Checker:
        """Starts the interactive web explorer (blocks). See
        ``stateright_tpu.explorer``."""
        try:
            from ..explorer import serve
        except ImportError as e:
            raise NotImplementedError(
                "the explorer module is not available in this build") from e

        return serve(self, addresses)

    def symmetry(self) -> "CheckerBuilder":
        """Enables symmetry reduction; model states must implement
        ``representative()`` (`checker.rs:149-153`)."""
        self.symmetry_fn(lambda state: state.representative())
        # The native DFS engine can honor the model's own representative
        # (it has a compiled copy) but not an arbitrary canonicalizer.
        self._symmetry_is_default = True
        return self

    def symmetry_fn(self, representative: Callable) -> "CheckerBuilder":
        """Enables symmetry reduction with an explicit canonicalizer."""
        self._symmetry = representative
        self._symmetry_is_default = False
        return self

    def target_state_count(self, count: int) -> "CheckerBuilder":
        """Approximate number of states to generate; the checker may exceed
        it, but never generates fewer if more exist."""
        self._target_state_count = count if count > 0 else None
        return self

    def threads(self, thread_count: int) -> "CheckerBuilder":
        """Worker count for the host engines (ignored by the TPU engine,
        which parallelizes over the frontier instead)."""
        self._thread_count = thread_count
        return self

    def visitor(self, visitor) -> "CheckerBuilder":
        """A function or ``CheckerVisitor`` run on each evaluated state."""
        self._visitor = visitor
        return self
