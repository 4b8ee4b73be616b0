"""Pallas kernels for the device wave: visited-table probe and the
single-kernel wave megakernel.

The BASELINE.json north star names an "HBM-resident hash table written
in Pallas" as the visited-set design. The XLA path
(`engine.dedup_and_insert`) runs the probe loop as a ``lax.while_loop``
whose per-round gathers and claim-scatters hit the table at HBM
latency; the round-5/7 kernel (``dedup_and_insert_pallas``) stages the
whole table into VMEM once, runs every probe round at VMEM latency,
and writes the table back once — the structure a TPU actually wants
for a probe chain. The capacity gate derives from the backend's
reported per-core VMEM budget when it exposes one, else from a table
keyed by ``device_kind`` (``_vmem_budget_bytes``); the engine degrades
to the XLA path above the gate and when Pallas is unavailable. On a
TPU the kernels are refused outright (``TPU_REFUSAL``): the TPU
compiler cannot pass their uint64 operands into Mosaic.

Two dedup levels run in the probe kernel (ISSUE 2): the intra-wave
*local dedup* (first-occurrence collapse of duplicate fingerprints
among the B*F candidates) and the global probe. By default the local
pass runs in-kernel against a VMEM scratch table (``fuse_local=True``)
— the GPUexplore observation that duplicate successors should die in
fast local memory before ever touching the global structure — using
the same sort-free scatter-min group resolution as
``engine.first_occurrence_candidates``; ``fuse_local=False`` keeps the
round-5 behavior (mask computed XLA-side, kernel is pure probe/claim)
for A/B and for backends where the fused lowering regresses.

**The wave megakernel (ISSUE 10).** ``build_wave_megakernel`` extends
the probe kernel into the whole successor path: one ``pallas_call``
runs in-kernel unpack of the packed ``uint32[Wp]`` storage rows
(``tpu/packing.py``), vmapped successor expansion (``DeviceModel.
step`` + boundary pruning), fingerprinting (``tpu/hashing.py`` mixes),
the in-VMEM first-occurrence local dedup, the global probe/claim
against the VMEM-staged visited table, and the re-pack of the
successor rows for storage — so between reading the packed batch and
writing the packed survivors, nothing touches HBM but the one table
round trip. ``build_sender_megakernel`` is the table-less front half
(expand → fingerprint → local dedup) the sharded engines run per shard
under ``shard_map``, where the visited table is partitioned and the
probe stays owner-side after the all-to-all.

Semantics are bit-identical to the XLA ladder in every case: the
kernels trace the ENGINE's own ``expand_frontier`` /
``fingerprint_successors`` / ``first_occurrence_candidates`` functions
and the shared probe/claim body (``_probe_claim``) inside the kernel,
so the bit-identity contract has exactly one implementation per stage;
the differential suites (``tests/test_wave_kernel.py``) pin counts,
discoveries, parent maps, and checkpoint payload bytes knob-on vs off
across all four engines. On the CPU backend the kernels run in Pallas
interpret mode (``pl.pallas_call(..., interpret=True)``) — correct but
not fast. On a TPU the engines refuse them (``refuse_on_tpu``).

Reference analog: the ``DashMap`` visited set of `bfs.rs:26,245-259`
plus the per-worker successor loop of `bfs.rs:75-152`, collapsed into
one device program (the BLEST/GPU-hash-table observation: per-level
BFS work belongs fused next to the table it probes).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .hashing import SENTINEL

__all__ = ["PALLAS_AVAILABLE", "TPU_REFUSAL", "refuse_on_tpu",
           "pallas_table_capacity_ok",
           "pallas_table_capacity_limit", "dedup_and_insert_pallas",
           "default_interpret", "wave_kernel_ok", "sender_kernel_ok",
           "wave_kernel_bytes", "build_wave_megakernel",
           "build_sender_megakernel"]

try:  # pallas ships with jax, but keep the engine loadable without it
    from jax.experimental import pallas as pl

    PALLAS_AVAILABLE = True
except ImportError:  # pragma: no cover - jax always bundles pallas here
    pl = None
    PALLAS_AVAILABLE = False

#: fraction of the reported VMEM budget the resident table may take —
#: the probe state (fps, candidate mask, indices, steps) and the local
#: dedup scratch must co-reside with it.
_VMEM_TABLE_FRACTION = 0.5

_CAPACITY_LIMIT_CACHE: list = []

#: fraction of the VMEM budget the megakernel's co-resident working set
#: (table + batch + successors + fps + scratch) may take — headroom for
#: the compiler's own spills and double-buffering.
_WAVE_KERNEL_VMEM_FRACTION = 0.9

#: per-core VMEM a kernel may use, by ``device_kind``, for devices that
#: report no budget. ``cpu`` is the interpret-mode stand-in, sized like
#: the TPU default. ``TPU v5 lite`` (v5e) takes Mosaic's default scoped
#: VMEM limit, 16 MiB. A kind missing here is an error, never a guess.
_VMEM_BYTES_BY_KIND = {"cpu": 16 << 20, "TPU v5 lite": 16 << 20}

#: Why the kernels in this module cannot run on a TPU. Their visited
#: table and fingerprints are uint64, and Mosaic has no 64-bit vectors.
#: Compiled for a described v5e (PR 21, tests/test_chip_compile.py), a
#: uint64 constant of 2^63 or more fails to lower (``IntegerAttr``);
#: with the constants made small, the uint64-to-int32 convert recurses
#: without end in the lowering; and a kernel that only adds uint64
#: vectors is refused by XLA itself, which cannot pass 64-bit operands
#: into a ``tpu_custom_call``.
TPU_REFUSAL = (
    "Mosaic has no 64-bit vectors, and XLA refuses uint64 operands of a "
    "tpu_custom_call (\"UNIMPLEMENTED: While rewriting computation to "
    "not contain X64 element types ...\"); the kernels' uint64 table "
    "and fingerprints need a uint32-pair layout first (ROADMAP A4)")

_BACKEND_DECISION_CACHE: list = []


def default_interpret() -> bool:
    """Whether pallas kernels on this process's default backend should
    run in interpret mode (every backend but TPU). Cached at module
    level: the backend is a process property, and
    ``dedup_and_insert_pallas`` used to re-derive it through
    ``jax.default_backend()`` on every dispatch-program trace."""
    if not _BACKEND_DECISION_CACHE:
        _BACKEND_DECISION_CACHE.append(jax.default_backend() != "tpu")
    return _BACKEND_DECISION_CACHE[0]


def refuse_on_tpu(what: str) -> None:
    """Raises where the kernels would have to lower for a TPU: they
    never run interpret mode there, and never swap silently to XLA."""
    if not default_interpret():
        raise NotImplementedError(
            f"{what} cannot run on a TPU: {TPU_REFUSAL}")


def _vmem_budget_bytes() -> int:
    """The per-core VMEM budget: what the device reports, else the
    ``device_kind`` table above. JAX has no stable cross-version API
    for this, so probe the known spellings (device attribute, then
    ``memory_stats()`` keys). Raises for a kind the table lacks.
    Note ``jax.local_devices()`` initializes the default backend if
    none exists yet."""
    device = jax.local_devices()[0]
    for attr in ("vmem_size_bytes", "core_vmem_size_bytes"):
        value = getattr(device, attr, None)
        if value:
            return int(value)
    stats = device.memory_stats() or {}
    for key in ("vmem_size_bytes", "vmem_bytes_limit",
                "vmem_bytes_reservable_limit"):
        if stats.get(key):
            return int(stats[key])
    try:
        return _VMEM_BYTES_BY_KIND[device.device_kind]
    except KeyError:
        raise NotImplementedError(
            f"no VMEM budget for device kind {device.device_kind!r}: "
            "the device reports none and pallas_table."
            "_VMEM_BYTES_BY_KIND has no entry") from None


def pallas_table_capacity_limit() -> int:
    """Largest table capacity (uint64 entries, power of two) the kernel
    will stage into VMEM, derived from the VMEM budget. Cached per
    process — the budget is a hardware property, and this is called per
    wave-program build."""
    if not _CAPACITY_LIMIT_CACHE:
        entries = max(1, int(_vmem_budget_bytes()
                             * _VMEM_TABLE_FRACTION) // 8)
        limit = 1 << (entries.bit_length() - 1)  # power-of-two floor
        _CAPACITY_LIMIT_CACHE.append(max(limit, 1 << 12))
    return _CAPACITY_LIMIT_CACHE[0]


def pallas_table_capacity_ok(capacity: int) -> bool:
    return PALLAS_AVAILABLE and capacity <= pallas_table_capacity_limit()


def _probe_claim(fps, candidate, table0, capacity: int):
    """The in-kernel global probe/claim loop over a VMEM-staged table
    value, shaped as batched probe *rounds* (arXiv:1712.09494): each
    while-loop round issues exactly ONE contiguous gather across the
    whole candidate block, serving both the claim resolutions deferred
    from the previous round and this round's probes, instead of the
    per-row probe → claim-scatter → verify-gather chain (two gathers a
    round). A row that observes an empty slot enters ``claiming`` and
    scatters its fingerprint at the START of the next round; the same
    round's single gather then tells it whether it won. Same slot/step
    functions and claim-scatter winner rule as ``engine.
    global_insert`` — the one probe implementation both the probe
    kernel and the wave megakernels trace. Returns ``(table,
    new_mask)``."""
    import numpy as np

    from .engine import _STEP_MIX, _TABLE_MIX

    # Plain numpy scalars: a closed-over traced jnp constant would be
    # rejected by pallas_call ("captures constants").
    sentinel = np.uint64(SENTINEL)
    shift = np.uint64(64 - (capacity.bit_length() - 1))
    slot_mask = np.int32(capacity - 1)
    idx0 = ((fps * np.uint64(_TABLE_MIX)) >> shift).astype(jnp.int32)
    step = (((fps * np.uint64(_STEP_MIX)) >> shift)
            .astype(jnp.int32) | 1)

    def cond(carry):
        _, _, pending, _, _ = carry
        # claiming is always a subset of pending (a claim resolves
        # before its row leaves the pending set), so one test suffices.
        return pending.any()

    def body(carry):
        table, idx, pending, claiming, is_new = carry
        # Claim-scatter for the rows that observed an empty slot last
        # round — then ONE gather across the block resolves those
        # claims AND probes every other pending row's current slot.
        table = table.at[jnp.where(claiming, idx, capacity)].set(
            fps, mode="drop")
        cur = table[idx]
        won = claiming & (cur == fps)
        lost = claiming & ~won
        probing = pending & ~claiming
        found = probing & (cur == fps)
        empty = probing & (cur == sentinel)
        is_new = is_new | won
        pending = pending & ~(found | won)
        claiming = empty
        # Losers and occupied-by-other probes advance their chain;
        # empty observers hold the slot index for next round's claim.
        advance = lost | (probing & ~found & ~empty)
        idx = jnp.where(advance, (idx + step) & slot_mask, idx)
        return table, idx, pending, claiming, is_new

    table, _, _, _, new_mask = jax.lax.while_loop(
        cond, body,
        (table0, idx0, candidate, jnp.zeros(fps.shape, bool),
         jnp.zeros(fps.shape, bool)))
    return table, new_mask


def _kernel(capacity: int, fuse_local: bool):
    def kernel(fps_ref, candidate_ref, table_in_ref, new_mask_ref,
               cand_mask_ref, table_out_ref):
        fps = fps_ref[:]
        if fuse_local:
            # Intra-wave first-occurrence against a scratch table in
            # the kernel's VMEM value domain — duplicates die here,
            # before the global table sees them. The ENGINE's function
            # traces directly inside the kernel (jnp ops only, all
            # constants created in-trace), so the bit-identity contract
            # has exactly one implementation.
            from .engine import first_occurrence_candidates

            candidate = first_occurrence_candidates(fps)
        else:
            candidate = candidate_ref[:]
        table, new_mask = _probe_claim(fps, candidate, table_in_ref[:],
                                       capacity)
        new_mask_ref[:] = new_mask
        cand_mask_ref[:] = candidate
        table_out_ref[:] = table

    return kernel


def dedup_and_insert_pallas(dedup_fps, visited, capacity: int,
                            interpret: Optional[bool] = None,
                            fuse_local: bool = True):
    """Drop-in for the ``engine.dedup_impl`` contract behind
    ``table_impl="pallas"``: returns ``(new_mask, new_count, cand_count,
    visited)``.

    ``interpret`` defaults to True off-TPU (the kernel still computes
    exactly; only the lowering differs). ``fuse_local`` runs the
    intra-wave local dedup inside the kernel (VMEM scratch); False
    computes it XLA-side as before — both bit-identical.
    """
    if not pallas_table_capacity_ok(capacity):
        raise ValueError(
            f"pallas table kernel supports capacities <= "
            f"{pallas_table_capacity_limit()} (got {capacity}); use the "
            "XLA table")
    from .engine import first_occurrence_candidates

    if interpret is None:
        interpret = default_interpret()
    n = dedup_fps.shape[0]
    if fuse_local:
        # The kernel ignores this operand; a cheap placeholder keeps the
        # call signature/kernel arity uniform across both variants.
        candidate = jnp.zeros((n,), jnp.bool_)
    else:
        candidate = first_occurrence_candidates(dedup_fps)
    new_mask, cand_mask, visited = pl.pallas_call(
        _kernel(capacity, fuse_local),
        out_shape=(
            jax.ShapeDtypeStruct((n,), jnp.bool_),
            jax.ShapeDtypeStruct((n,), jnp.bool_),
            jax.ShapeDtypeStruct((capacity,), jnp.uint64),
        ),
        input_output_aliases={2: 2},  # table updated in place
        interpret=interpret,
    )(dedup_fps, candidate, visited)
    return (new_mask, jnp.sum(new_mask, dtype=jnp.int32),
            jnp.sum(cand_mask, dtype=jnp.int32), visited)


# -- The single-kernel wave (ISSUE 10) ------------------------------------

def wave_kernel_bytes(batch: int, fanout: int, width: int,
                      row_width: int, capacity: int = 0,
                      extra_bytes: int = 0) -> int:
    """Conservative VMEM bytes the megakernel's working set co-resides
    in: the staged table (``capacity`` entries; 0 for the table-less
    sender variant), the packed batch + its unpacked registers, the
    full successor window in both forms, the fingerprint pairs, the
    probe state, and the first-occurrence scratch (a power-of-two table
    of >= 2S int32 slots). ``extra_bytes`` adds a caller-enumerated
    term — the matmul-wave plan's transition tables plus its widest
    one-hot block (``matmul_wave.plan_bytes``) when the expand stage
    runs in matmul form. Everything is enumerated — the gate compares
    the total against the budget instead of reserving a blanket
    fraction for "the rest" like the table-only gate does."""
    s = batch * fanout
    scratch = 1 << max(int(s - 1).bit_length() + 1, 4)  # >= 2S slots
    return (8 * capacity                       # visited table
            + 4 * batch * (width + row_width)  # batch: packed + registers
            + 4 * s * (width + row_width)      # successors, both forms
            + 16 * s                           # dedup + path fingerprints
            + 8 * s                            # probe idx + step (int32)
            + 16 * s                           # masks / pending lanes
            + 4 * scratch                      # local-dedup scratch
            + extra_bytes)                     # caller extras (matmul)


def wave_kernel_ok(capacity: int, batch: int, fanout: int, width: int,
                   row_width: int, extra_bytes: int = 0) -> bool:
    """Whether the full megakernel (with the table staged in VMEM) fits
    this backend at this (batch, capacity). The engines degrade to the
    XLA ladder above the gate — mid-run table growth must never kill a
    checker, exactly like the probe-kernel gate."""
    return (PALLAS_AVAILABLE
            and wave_kernel_bytes(batch, fanout, width, row_width,
                                  capacity, extra_bytes)
            <= _WAVE_KERNEL_VMEM_FRACTION * _vmem_budget_bytes())


def sender_kernel_ok(batch: int, fanout: int, width: int,
                     row_width: int, extra_bytes: int = 0) -> bool:
    """The table-less gate for the sharded engines' sender-side kernel
    (expand → fingerprint → local dedup; the partitioned table is
    probed owner-side after the all-to-all)."""
    return (PALLAS_AVAILABLE
            and wave_kernel_bytes(batch, fanout, width, row_width, 0,
                                  extra_bytes)
            <= _WAVE_KERNEL_VMEM_FRACTION * _vmem_budget_bytes())


def _wave_front(dm, use_sym: bool, layout, store_rows, valid,
                matmul_plan=None, matmul_tables=None):
    """The kernel-traced front half shared by both megakernels: unpack
    the packed storage rows to register lanes, expand, fingerprint.
    Traces the ENGINE's own functions so every stage has exactly one
    implementation (the bit-identity contract). With ``matmul_plan``
    the expand stage traces ``matmul_wave.matmul_expand`` instead of
    the vmapped ``dm.step`` — in-kernel the one-hot registers live in
    VMEM and the per-action transition tables (``matmul_tables``, one
    kernel operand per key group: a pallas kernel may not close over
    array constants) are exactly the dense operands Mosaic can put on
    the MXU."""
    from .engine import expand_frontier, fingerprint_successors
    from .matmul_wave import matmul_expand

    reg = store_rows if layout is None else layout.unpack(store_rows)
    succ_flat, sflat, _, _ = (
        matmul_expand(dm, matmul_plan, reg, valid,
                      tables=matmul_tables)
        if matmul_plan is not None
        else expand_frontier(dm, reg, valid))
    dedup_fps, path_fps = fingerprint_successors(dm, succ_flat, sflat,
                                                 use_sym)
    succ_store = succ_flat if layout is None else layout.pack(succ_flat)
    return succ_store, dedup_fps, path_fps, sflat


def build_wave_megakernel(dm, batch: int, capacity: int,
                          use_sym: bool = False, layout=None,
                          interpret: Optional[bool] = None,
                          matmul_plan=None):
    """One ``pallas_call`` for the whole successor path of a wave::

        mega(vecs: uint32[B, Wr], valid: bool[B], visited: uint64[C])
          -> (succ_store: uint32[B*F, Wr], path_fps: uint64[B*F],
              sflat: bool[B*F], new_mask: bool[B*F],
              cand_mask: bool[B*F], visited: uint64[C])

    In-kernel stages: unpack (``layout`` — the packed rows are what
    rides HBM; registers exist only in VMEM), vmapped ``dm.step`` +
    boundary pruning, the hashing.py fingerprint mixes, the
    first-occurrence local dedup, the global probe/claim against the
    VMEM-staged table (``_probe_claim``), and the storage re-pack of
    the successor window. Scalar reductions (successor/novel counts,
    terminal rows) and the ladder's K-row compaction stay XLA-side —
    they are cheap and their outputs cross to the host anyway.

    ``visited`` is aliased in-place (the engines' donation contract).
    The caller gates with ``wave_kernel_ok`` first; ``interpret``
    defaults to the cached backend decision (interpret off-TPU)."""
    B, F, W = batch, dm.max_fanout, dm.state_width
    Wr = W if layout is None else layout.packed_width
    S = B * F
    n_tab = 0 if matmul_plan is None else len(matmul_plan.groups)
    if interpret is None:
        interpret = default_interpret()

    def kernel(vecs_ref, valid_ref, table_in_ref, *refs):
        from .engine import first_occurrence_candidates

        tabs = [r[:] for r in refs[:n_tab]] if n_tab else None
        (succ_ref, pfp_ref, sflat_ref, new_mask_ref, cand_mask_ref,
         table_out_ref) = refs[n_tab:]
        succ_store, dedup_fps, path_fps, sflat = _wave_front(
            dm, use_sym, layout, vecs_ref[:], valid_ref[:],
            matmul_plan=matmul_plan, matmul_tables=tabs)
        candidate = first_occurrence_candidates(dedup_fps)
        table, new_mask = _probe_claim(dedup_fps, candidate,
                                       table_in_ref[:], capacity)
        succ_ref[:] = succ_store
        pfp_ref[:] = path_fps
        sflat_ref[:] = sflat
        new_mask_ref[:] = new_mask
        cand_mask_ref[:] = candidate
        table_out_ref[:] = table

    def mega(vecs, valid, visited):
        # The plan's transition tables ride as trailing operands (a
        # pallas kernel may not capture array constants).
        tabs = ([jnp.asarray(g.table) for g in matmul_plan.groups]
                if n_tab else [])
        return pl.pallas_call(
            kernel,
            out_shape=(
                jax.ShapeDtypeStruct((S, Wr), jnp.uint32),
                jax.ShapeDtypeStruct((S,), jnp.uint64),
                jax.ShapeDtypeStruct((S,), jnp.bool_),
                jax.ShapeDtypeStruct((S,), jnp.bool_),
                jax.ShapeDtypeStruct((S,), jnp.bool_),
                jax.ShapeDtypeStruct((capacity,), jnp.uint64),
            ),
            input_output_aliases={2: 5},  # table updated in place
            interpret=interpret,
        )(vecs, valid, visited, *tabs)

    return mega


def build_sender_megakernel(dm, batch: int, use_sym: bool = False,
                            layout=None, local_dedup: bool = True,
                            interpret: Optional[bool] = None,
                            matmul_plan=None):
    """The sharded engines' per-shard kernel — the megakernel's front
    half, no table::

        sender(vecs: uint32[B, Wr], valid: bool[B])
          -> (succ_store: uint32[B*F, Wr], dedup_fps: uint64[B*F],
              path_fps: uint64[B*F], sflat: bool[B*F],
              send_mask: bool[B*F])

    ``dedup_fps`` drives the owner routing of the all-to-all;
    ``send_mask`` is the sender-side first-occurrence mask when
    ``local_dedup`` (the ``exchange_novel_only`` contract) and plainly
    ``sflat`` otherwise. The global probe/claim stays owner-side (the
    visited table is partitioned across the mesh). Runs per shard
    under ``shard_map``; gate with ``sender_kernel_ok``."""
    B, F, W = batch, dm.max_fanout, dm.state_width
    Wr = W if layout is None else layout.packed_width
    S = B * F
    n_tab = 0 if matmul_plan is None else len(matmul_plan.groups)
    if interpret is None:
        interpret = default_interpret()

    def kernel(vecs_ref, valid_ref, *refs):
        from .engine import first_occurrence_candidates

        tabs = [r[:] for r in refs[:n_tab]] if n_tab else None
        (succ_ref, dfp_ref, pfp_ref, sflat_ref,
         send_ref) = refs[n_tab:]
        succ_store, dedup_fps, path_fps, sflat = _wave_front(
            dm, use_sym, layout, vecs_ref[:], valid_ref[:],
            matmul_plan=matmul_plan, matmul_tables=tabs)
        send = (first_occurrence_candidates(dedup_fps) if local_dedup
                else sflat)
        succ_ref[:] = succ_store
        dfp_ref[:] = dedup_fps
        pfp_ref[:] = path_fps
        sflat_ref[:] = sflat
        send_ref[:] = send

    def sender(vecs, valid):
        tabs = ([jnp.asarray(g.table) for g in matmul_plan.groups]
                if n_tab else [])
        return pl.pallas_call(
            kernel,
            out_shape=(
                jax.ShapeDtypeStruct((S, Wr), jnp.uint32),
                jax.ShapeDtypeStruct((S,), jnp.uint64),
                jax.ShapeDtypeStruct((S,), jnp.uint64),
                jax.ShapeDtypeStruct((S,), jnp.bool_),
                jax.ShapeDtypeStruct((S,), jnp.bool_),
            ),
            interpret=interpret,
        )(vecs, valid, *tabs)

    return sender
