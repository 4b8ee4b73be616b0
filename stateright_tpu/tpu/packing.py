"""Model-derived bit-packed row format for device state storage.

Device states are *computed* as ``uint32[state_width]`` registers (the
``DeviceModel`` contract), but most models declare lanes far narrower
than 32 bits — a 2pc RM state is 2 bits, a paxos ballot index 4 — so
storing, probing, exchanging, and checkpointing full-width rows moves
3-4x the bytes the encoding needs. Explicit-state checking on
accelerators is bandwidth-bound (GPUexplore, arXiv:1801.05857; ScalaBFS,
arXiv:2105.11754: HBM traffic, not FLOPs, is the currency), so the
engines keep rows *packed* at rest and unpack to registers only inside
the wave.

This module is the layout compiler: :func:`compile_layout` turns a
model's :meth:`DeviceModel.lane_bits` declaration into a static
word-aligned bitfield plan and emits matching jittable
``pack(uint32[..., W]) -> uint32[..., Wp]`` / ``unpack`` programs
(``Wp = ceil(sum(bits) / 32)``) plus numpy twins for the host-side cold
paths (seeding, checkpoint conversion). Compute is untouched: ``step``,
properties, fingerprints, and symmetry rewrites always see the exact
unpacked lanes, so counts, discoveries, and parent maps are
bit-identical with packing on or off (the pack-matrix suite pins this).

Lane specs (one per lane, in lane order):

- ``b`` (int, 1..32): a plain lane whose values fit ``b`` bits. The
  declared width is part of the encoding contract, like injectivity —
  packing truncates silently beyond it (``pack_np_checked`` exists for
  cold-path validation).
- ``(b, sentinel)``: a lane over ``[0, 2^b - 1)`` plus one out-of-band
  sentinel value (e.g. an actor network slot's ``EMPTY_ENV`` =
  ``0xFFFFFFFF``). The sentinel packs as the field's all-ones pattern
  and unpacks back exactly; real values must stay strictly below
  ``2^b - 1``.

Invalid specs (bits out of range, wrong lane count, a sentinel that
collides with the value range) are rejected here, at build time — never
as silent corruption mid-run.

**Tenant lane (round 16).** The wave multiplexer stores rows from many
co-scheduled jobs in one frontier, so a packed row must say which job it
belongs to. :meth:`PackedLayout.with_tenant_lane` derives a layout whose
rows carry one extra *word-aligned* trailing lane holding a small tenant
slot index. The model lanes' placement, widths, and sentinel rules are
byte-for-byte unchanged (the tenant lane starts on its own fresh word),
so stripping the trailing word recovers exactly the solo storage row —
which is how multiplexed checkpoints stay bit-identical to solo ones.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np

__all__ = ["PackedLayout", "compile_layout"]


class _Lane:
    __slots__ = ("bits", "word", "offset", "sentinel", "spill")

    def __init__(self, bits: int, word: int, offset: int,
                 sentinel: Optional[int]):
        self.bits = bits
        self.word = word          # first packed word holding this lane
        self.offset = offset      # bit offset within that word
        self.sentinel = sentinel  # unpacked value of the all-ones field
        self.spill = offset + bits > 32  # straddles into word+1


def _parse_spec(spec, i: int) -> Tuple[int, Optional[int]]:
    if isinstance(spec, (tuple, list)):
        if len(spec) != 2:
            raise ValueError(
                f"lane {i}: spec {spec!r} must be `bits` or "
                "`(bits, sentinel)`")
        bits, sentinel = int(spec[0]), int(spec[1])
    else:
        bits, sentinel = int(spec), None
    if not 1 <= bits <= 32:
        raise ValueError(
            f"lane {i}: declared width {bits} outside 1..32")
    if sentinel is not None:
        if not 0 <= sentinel < (1 << 32):
            raise ValueError(
                f"lane {i}: sentinel {sentinel} is not a uint32")
        if bits == 32:
            # A 32-bit field represents everything; a sentinel adds
            # nothing and the all-ones reservation would be a lie.
            sentinel = None
        elif sentinel < (1 << bits) - 1:
            raise ValueError(
                f"lane {i}: sentinel {sentinel} collides with the "
                f"{bits}-bit value range (must be >= {(1 << bits) - 1})")
    return bits, sentinel


class PackedLayout:
    """A compiled word-aligned bitfield plan for one model's rows.

    ``packs`` is False when the plan saves nothing (every lane 32 bits,
    or ``Wp == W``); the engines then skip packing entirely and this
    object degrades to an identity codec.
    """

    def __init__(self, specs: Sequence, state_width: int):
        specs = list(specs)
        if len(specs) != state_width:
            raise ValueError(
                f"lane_bits declares {len(specs)} lanes; the model's "
                f"state_width is {state_width}")
        self.width = state_width
        self.lanes: List[_Lane] = []
        cursor = 0
        for i, spec in enumerate(specs):
            bits, sentinel = _parse_spec(spec, i)
            self.lanes.append(
                _Lane(bits, cursor // 32, cursor % 32, sentinel))
            cursor += bits
        self.total_bits = cursor
        self.packed_width = max(1, -(-cursor // 32))
        self.packs = self.packed_width < self.width
        #: JSON-serializable form (checkpoint headers self-describe
        #: their layout with this).
        self.specs = [(l.bits if l.sentinel is None
                       else [l.bits, l.sentinel]) for l in self.lanes]
        #: set by :meth:`with_tenant_lane` on derived layouts; the base
        #: layout compiled from a model never has one.
        self.tenant_lane: Optional[_Lane] = None
        self._jit_pack = None
        self._jit_unpack = None

    def with_tenant_lane(self, bits: int = 16) -> "PackedLayout":
        """Derives a layout whose packed rows grow one trailing
        word-aligned lane carrying a tenant (job) slot index.

        The model lanes are re-laid out identically — same words, same
        offsets, same sentinels — and the tenant lane occupies its own
        fresh word after them, so ``packed[..., :-1]`` of a tenant row
        is exactly the row the base layout would have produced."""
        if self.tenant_lane is not None:
            raise ValueError("layout already carries a tenant lane")
        if not 1 <= int(bits) <= 32:
            raise ValueError(
                f"tenant lane width {bits} outside 1..32")
        out = PackedLayout(self.specs, self.width)
        out.tenant_lane = _Lane(int(bits), out.packed_width, 0, None)
        out.packed_width += 1
        return out

    # -- numpy codec (host cold paths) -----------------------------------

    def pack_np(self, rows: np.ndarray) -> np.ndarray:
        """``uint32[..., W] -> uint32[..., Wp]`` (vectorized numpy)."""
        rows = np.asarray(rows, np.uint32)
        out = np.zeros(rows.shape[:-1] + (self.packed_width,), np.uint32)
        for i, l in enumerate(self.lanes):
            mask = np.uint32((1 << l.bits) - 1) if l.bits < 32 \
                else np.uint32(0xFFFFFFFF)
            v = rows[..., i]
            f = (np.minimum(v, mask) if l.sentinel is not None
                 else v & mask)
            out[..., l.word] |= (f << np.uint32(l.offset)).astype(
                np.uint32)
            if l.spill:
                out[..., l.word + 1] |= (
                    f >> np.uint32(32 - l.offset)).astype(np.uint32)
        return out

    def unpack_np(self, packed: np.ndarray) -> np.ndarray:
        """``uint32[..., Wp] -> uint32[..., W]`` (vectorized numpy)."""
        packed = np.asarray(packed, np.uint32)
        out = np.zeros(packed.shape[:-1] + (self.width,), np.uint32)
        for i, l in enumerate(self.lanes):
            out[..., i] = self._lane_np(packed, l)
        return out

    def _lane_np(self, packed: np.ndarray, l: _Lane) -> np.ndarray:
        mask = np.uint32((1 << l.bits) - 1) if l.bits < 32 \
            else np.uint32(0xFFFFFFFF)
        f = packed[..., l.word] >> np.uint32(l.offset)
        if l.spill:
            f = f | (packed[..., l.word + 1]
                     << np.uint32(32 - l.offset)).astype(np.uint32)
        f = f & mask
        if l.sentinel is not None:
            f = np.where(f == mask, np.uint32(l.sentinel), f)
        return f.astype(np.uint32)

    def lane_np(self, packed: np.ndarray, lane: int) -> np.ndarray:
        """One unpacked lane column from packed rows (e.g. the engine's
        error-lane check) without materializing the full unpack."""
        return self._lane_np(packed, self.lanes[lane])

    def tenant_np(self, packed: np.ndarray) -> np.ndarray:
        """The tenant slot column of tenant-lane rows (numpy)."""
        if self.tenant_lane is None:
            raise ValueError("layout has no tenant lane")
        return self._lane_np(np.asarray(packed, np.uint32),
                             self.tenant_lane)

    def pack_tenant_np(self, rows: np.ndarray,
                       tags: np.ndarray) -> np.ndarray:
        """``(uint32[..., W], tag[...]) -> uint32[..., Wp+1]``: packs
        model lanes exactly as the base layout would, then writes the
        tenant slot into the trailing word (numpy)."""
        if self.tenant_lane is None:
            raise ValueError("layout has no tenant lane")
        out = self.pack_np(rows)
        l = self.tenant_lane
        mask = np.uint32((1 << l.bits) - 1) if l.bits < 32 \
            else np.uint32(0xFFFFFFFF)
        out[..., l.word] = np.asarray(tags, np.uint32) & mask
        return out

    def check_fits(self, rows: np.ndarray) -> None:
        """Raises if any lane value exceeds its declared width — the
        cold-path guard (seeding, checkpoint conversion) for a model
        whose ``lane_bits`` contract is wrong."""
        rows = np.asarray(rows, np.uint32)
        for i, l in enumerate(self.lanes):
            if l.bits == 32:
                continue
            mask = np.uint32((1 << l.bits) - 1)
            v = rows[..., i]
            bad = (v > mask) if l.sentinel is None else \
                ((v >= mask) & (v != np.uint32(l.sentinel)))
            if bad.any():
                raise ValueError(
                    f"lane {i} holds value {int(v[bad.nonzero()][0])}, "
                    f"outside its declared {l.bits}-bit width — the "
                    "model's lane_bits() contract is wrong")

    # -- jittable codec (wave programs) ----------------------------------

    def pack(self, rows):
        """``uint32[..., W] -> uint32[..., Wp]`` (traceable jnp)."""
        import jax.numpy as jnp

        words = [jnp.zeros(rows.shape[:-1], jnp.uint32)
                 for _ in range(self.packed_width)]
        for i, l in enumerate(self.lanes):
            mask = jnp.uint32((1 << l.bits) - 1) if l.bits < 32 \
                else jnp.uint32(0xFFFFFFFF)
            v = rows[..., i]
            f = (jnp.minimum(v, mask) if l.sentinel is not None
                 else v & mask)
            words[l.word] = words[l.word] | (f << l.offset)
            if l.spill:
                words[l.word + 1] = words[l.word + 1] \
                    | (f >> (32 - l.offset))
        return jnp.stack(words, axis=-1)

    def unpack(self, packed):
        """``uint32[..., Wp] -> uint32[..., W]`` (traceable jnp)."""
        import jax.numpy as jnp

        return jnp.stack(
            [self._lane(packed, l) for l in self.lanes], axis=-1)

    def _lane(self, packed, l: _Lane):
        import jax.numpy as jnp

        mask = jnp.uint32((1 << l.bits) - 1) if l.bits < 32 \
            else jnp.uint32(0xFFFFFFFF)
        f = packed[..., l.word] >> l.offset
        if l.spill:
            f = f | (packed[..., l.word + 1] << (32 - l.offset))
        f = f & mask
        if l.sentinel is not None:
            f = jnp.where(f == mask, jnp.uint32(l.sentinel), f)
        return f

    def lane(self, packed, lane: int):
        """One unpacked lane from packed rows (traceable jnp)."""
        return self._lane(packed, self.lanes[lane])

    def tenant(self, packed):
        """The tenant slot column of tenant-lane rows (traceable jnp)."""
        if self.tenant_lane is None:
            raise ValueError("layout has no tenant lane")
        return self._lane(packed, self.tenant_lane)

    def pack_tenant(self, rows, tags):
        """``(uint32[..., W], tag[...]) -> uint32[..., Wp+1]``: the
        traceable twin of :meth:`pack_tenant_np`."""
        import jax.numpy as jnp

        if self.tenant_lane is None:
            raise ValueError("layout has no tenant lane")
        l = self.tenant_lane
        mask = jnp.uint32((1 << l.bits) - 1) if l.bits < 32 \
            else jnp.uint32(0xFFFFFFFF)
        return self.pack(rows).at[..., l.word].set(
            tags.astype(jnp.uint32) & mask)

    def __repr__(self) -> str:
        return (f"PackedLayout(W={self.width}, Wp={self.packed_width}, "
                f"bits={self.total_bits}, packs={self.packs})")


def compile_layout(lane_bits, state_width: int) -> PackedLayout:
    """Compiles a model's ``lane_bits()`` declaration into a
    :class:`PackedLayout`. ``None`` (the conservative default: 32 bits
    per lane) yields the identity layout (``packs`` False)."""
    if lane_bits is None:
        lane_bits = [32] * state_width
    return PackedLayout(lane_bits, state_width)
