"""Transition-structure compiler: matmul-form frontier expansion (ISSUE 15).

The round-15 megakernel fused the successor path into one kernel, but
the work inside it is still gather/scatter on the vector unit — the MXU
sits idle. BLEST (arXiv:2512.21967) reformulates BFS frontier expansion
as matmul-friendly products; this module applies the idea to the wave
pipeline's ``expand_frontier`` stage for *regular* models.

A model is **regular** when, for every action ``a`` and every output
position ``o`` (each successor lane plus the action's validity bit),
the next-value function depends only on a small *key tuple* of input
lanes whose joint domain — the product of the declared ``lane_bits()``
widths — is enumerable. The compiler discovers the key tuples by
probing the model's own jitted ``step`` (sweep each lane over its full
declared domain at several random baseline contexts), tabulates each
key group by enumerating its joint domain, and verifies every table
row at independent random contexts; a verification miss refines the
key set with the offending lane and retries. Everything the compiler
knows comes from evaluating ``step`` itself, so the emitted tables are
exact by construction wherever the key-dependence inference is right,
and the independent-context verification plus the differential fuzz
suite (tests/test_matmul_wave.py) guard the inference.

At runtime (:func:`matmul_expand`) each key group advances the whole
batch with ONE dense product: the joint key index is one-hot encoded
``[B, D]`` and multiplied against the group's transition table
``[D, 2*n_cols]`` — exactly the shape Mosaic puts on the MXU. Bit
exactness on a float unit comes from a 16-bit lo/hi split: every table
entry is < 2^16, the one-hot selects exactly one row, and f32
represents integers below 2^24 exactly, so the uint32 reconstruction
``lo | (hi << 16)`` reproduces ``step``'s output bit for bit.

Irregular models (undeclared ``lane_bits``, sentinel lanes, key
domains past the cap, unstable inference) keep the vmapped ``step``
path via the capability gate: :func:`classify` always returns a stable
human-readable ``reason`` naming the first failed gate, which the
engines surface through ``scheduler_stats()["wave_matmul"]``.
"""

from __future__ import annotations

import itertools
from typing import List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

__all__ = [
    "KEY_DOMAIN_CAP", "LANE_DOMAIN_CAP", "MatmulClassification",
    "MatmulPlan", "classify", "matmul_expand", "plan_bytes",
]

#: Joint key-domain cap per output group: ∏ 2^bits over the key lanes.
#: Past this the transition table stops being a small VMEM-resident
#: constant and the one-hot matmul stops being a win.
KEY_DOMAIN_CAP = 4096
#: Single-lane domain cap for the probing sweep (a lane wider than this
#: cannot be swept exhaustively, and could never be a key lane anyway).
LANE_DOMAIN_CAP = 1 << 12
#: Baseline contexts for the dependence sweep / verification contexts
#: for the table build (independent draws, deterministic seed).
_N_BASELINES = 3
_N_VERIFY = 3
#: Key-set refinement rounds per output column before declaring the
#: inference unstable (each round adds one key lane, so a column can
#: never need more rounds than there are lanes).
_MAX_REFINE_PER_COL = 8
#: Per-group probe-row budget for the closure verification (the joint
#: domain times the non-key sweep width); past this the classification
#: itself would cost more than it buys.
_GROUP_PROBE_CAP = 1 << 19
#: Total row budget for the pairwise dependence sweep.
_PAIR_PROBE_CAP = 1 << 19
#: Fixed probe-batch shape: one jitted ``vmap(step)`` compile serves
#: every probe, padded to this many rows.
_CHUNK = 512


class _Group:
    """One key tuple and every (action, output) column it drives.

    ``table`` is float32 ``[domain, 2*len(cols)]`` — interleaved
    (lo, hi) 16-bit halves of the uint32 output value per column
    (validity columns carry 0/1 in the lo half). ``strides`` maps a key
    assignment to its table row: ``row = Σ lane_value[k] * stride[k]``,
    matching the enumeration order the table was built in."""

    __slots__ = ("keys", "strides", "domain", "cols", "table")

    def __init__(self, keys: Tuple[int, ...], strides: Tuple[int, ...],
                 domain: int, cols: List[Tuple[int, int]],
                 table: np.ndarray):
        self.keys = keys
        self.strides = strides
        self.domain = domain
        self.cols = cols
        self.table = table


class MatmulPlan:
    """A compiled matmul-form expansion for one regular model.

    ``groups`` carry the transition tables; ``consts`` are outputs with
    an empty key set (written as broadcast scalars, no matmul);
    ``copies`` (passthrough columns — the table turned out to be the
    identity on the output's own lane) are implicit: the runtime starts
    from a broadcast copy of the input registers, so they cost nothing.
    ``matmul_ops`` is the per-frontier-row MAC count, Σ_g D_g·2·n_g —
    the static gauge the wave events and bench record."""

    __slots__ = ("width", "fanout", "groups", "consts", "copies",
                 "matmul_ops", "table_bytes")

    def __init__(self, width: int, fanout: int, groups: List[_Group],
                 consts: List[Tuple[int, int, int]], copies: int):
        self.width = width
        self.fanout = fanout
        self.groups = groups
        self.consts = consts
        self.copies = copies
        self.matmul_ops = sum(g.domain * g.table.shape[1]
                              for g in groups)
        self.table_bytes = sum(g.table.nbytes for g in groups)


class MatmulClassification:
    """The capability-gate verdict: ``regular`` + a stable ``reason``
    string (pinned by tests), and the :class:`MatmulPlan` when
    regular."""

    __slots__ = ("regular", "reason", "plan")

    def __init__(self, regular: bool, reason: str,
                 plan: Optional[MatmulPlan]):
        self.regular = regular
        self.reason = reason
        self.plan = plan


def plan_bytes(plan: Optional[MatmulPlan], batch: int) -> int:
    """The matmul path's extra VMEM working set at ``batch`` rows: the
    widest one-hot block plus every resident transition table — the
    term the megakernel's VMEM gate adds when the plan rides
    in-kernel."""
    if plan is None:
        return 0
    widest = max((g.domain for g in plan.groups), default=0)
    return 4 * batch * widest + plan.table_bytes


def _irregular(reason: str) -> MatmulClassification:
    return MatmulClassification(False, reason, None)


class _StepProbe:
    """Batched host-side evaluator over the model's own ``step``: one
    fixed-shape jitted vmap, every probe padded to ``_CHUNK`` rows."""

    def __init__(self, dm):
        self._fn = jax.jit(jax.vmap(dm.step))

    def __call__(self, rows: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """``rows`` uint32 [N, W] → (succ uint32 [N, F, W],
        valid bool [N, F])."""
        succ_parts, val_parts = [], []
        for i in range(0, rows.shape[0], _CHUNK):
            chunk = rows[i:i + _CHUNK]
            n = chunk.shape[0]
            if n < _CHUNK:
                chunk = np.concatenate(
                    [chunk, np.repeat(chunk[-1:], _CHUNK - n, axis=0)])
            s, v = self._fn(jnp.asarray(chunk, jnp.uint32))
            succ_parts.append(np.asarray(s)[:n])
            val_parts.append(np.asarray(v)[:n])
        return (np.concatenate(succ_parts, axis=0),
                np.concatenate(val_parts, axis=0))


def _outputs(succ: np.ndarray, valid: np.ndarray) -> np.ndarray:
    """Stacks successor lanes and the validity bit into one uint32
    output cube ``[N, F, W+1]`` — column ``W`` is the action's validity
    (0/1), so key inference and tabulation treat it like any lane."""
    return np.concatenate(
        [succ, valid[..., None].astype(np.uint32)], axis=2)


def _random_contexts(rng, bits: Sequence[int], n: int) -> np.ndarray:
    """``n`` uniform in-domain probe rows (uint32 [n, W])."""
    cols = [rng.integers(0, 1 << b, size=n, dtype=np.uint32)
            for b in bits]
    return np.stack(cols, axis=1)


def _spread_contexts(rng, bits: Sequence[int], n: int) -> np.ndarray:
    """``n`` in-domain rows where every row past the first differs
    from row 0 in EVERY lane (a nonzero per-lane offset mod the lane
    domain) — so an output that secretly reads a lane outside its
    inferred key set sees that lane move in every verification
    context, not only with 1 - 1/D probability."""
    rows = _random_contexts(rng, bits, n)
    for j in range(1, n):
        for lane, b in enumerate(bits):
            d = 1 << b
            off = rng.integers(1, d) if d > 1 else 0
            rows[j, lane] = (rows[0, lane] + off) % d
    return rows


def _find_offender(probe: _StepProbe, vec_a: np.ndarray,
                   vec_b: np.ndarray, keys: Tuple[int, ...],
                   a: int, o: int) -> Optional[int]:
    """Two contexts that disagree on output ``(a, o)`` at identical key
    values: morph ``vec_a`` into ``vec_b`` one non-key lane at a time
    and return the first lane whose flip moves the output — the lane
    the key set is missing."""
    lanes = [l for l in range(vec_a.shape[0]) if l not in keys]
    rows = np.empty((len(lanes) + 1, vec_a.shape[0]), np.uint32)
    rows[0] = vec_a
    cur = vec_a.copy()
    for j, lane in enumerate(lanes):
        cur[lane] = vec_b[lane]
        rows[j + 1] = cur
    succ, valid = probe(rows)
    out = _outputs(succ, valid)[:, a, o]
    for j, lane in enumerate(lanes):
        if out[j + 1] != out[j]:
            return lane
    return None


#: Classification memo: probing a model costs thousands of step
#: evaluations plus one vmap compile, and engines classify at spawn
#: time. Keyed on the model's canonical form (``native_form()`` —
#: the same identity the cross-engine program cache trusts); ad-hoc
#: models without one re-classify every time.
_CLASSIFY_CACHE: dict = {}


def classify(dm) -> MatmulClassification:
    """Classifies ``dm`` and compiles its :class:`MatmulPlan` when
    regular. Deterministic: fixed probe seed, stable reason strings."""
    key = None
    try:
        nf = getattr(dm, "native_form", lambda: None)()
    except Exception:
        nf = None
    if nf is not None:
        model_id, params = nf
        key = (type(dm).__name__, model_id, tuple(params))
        hit = _CLASSIFY_CACHE.get(key)
        if hit is not None:
            return hit
    res = _classify(dm)
    if key is not None:
        _CLASSIFY_CACHE[key] = res
    return res


def _classify(dm) -> MatmulClassification:
    from .packing import compile_layout

    W, F = dm.state_width, dm.max_fanout
    lane_bits = getattr(dm, "lane_bits", lambda: None)()
    if lane_bits is None:
        return _irregular("undeclared lane_bits")
    layout = compile_layout(lane_bits, W)
    if any(lane.sentinel is not None for lane in layout.lanes):
        return _irregular("sentinel lane domains")
    bits = [lane.bits for lane in layout.lanes]
    for i, b in enumerate(bits):
        if (1 << b) > LANE_DOMAIN_CAP:
            return _irregular(
                f"lane domain too large (lane {i}: {b} bits)")

    probe = _StepProbe(dm)
    rng = np.random.default_rng(0)
    baselines = _spread_contexts(rng, bits, _N_BASELINES)
    base_out = _outputs(*probe(baselines))  # [R, F, W+1]

    # Dependence sweep: every lane over its full declared domain at
    # every baseline — one probe pass serves all F*(W+1) outputs.
    sweep_rows = []
    for lane in range(W):
        d = 1 << bits[lane]
        block = np.repeat(baselines, d, axis=0)  # [R*d, W]
        block[:, lane] = np.tile(
            np.arange(d, dtype=np.uint32), _N_BASELINES)
        sweep_rows.append(block)
    sweep_out = _outputs(*probe(np.concatenate(sweep_rows, axis=0)))

    deps: List[List[set]] = [[set() for _ in range(W + 1)]
                             for _ in range(F)]
    offset = 0
    for lane in range(W):
        d = 1 << bits[lane]
        block = sweep_out[offset:offset + _N_BASELINES * d]
        block = block.reshape(_N_BASELINES, d, F, W + 1)
        # Lane `lane` drives output (a, o) iff sweeping it moved the
        # output away from the baseline value anywhere.
        moved = (block != base_out[:, None]).any(axis=(0, 1))  # [F, W+1]
        for a, o in zip(*np.nonzero(moved)):
            deps[int(a)][int(o)].add(lane)
        offset += _N_BASELINES * d

    # Pairwise joint sweep (2-deviation probes): a conjunctive
    # dependence — e.g. 2pc's TmCommit validity, (tm == 0) &
    # (prepared == full) — is invisible to every single-lane sweep
    # from a context where the other conjunct is false. Sweeping each
    # lane PAIR over its joint domain at one baseline closes that gap
    # (the regularity criterion this compiler implements: dependence
    # must be revealable by at most two simultaneous lane deviations;
    # the closure verification below then grows key sets one lane at
    # a time from there).
    pair_total = sum((1 << bits[l1]) * (1 << bits[l2])
                     for l1 in range(W) for l2 in range(l1 + 1, W))
    if pair_total > _PAIR_PROBE_CAP:
        return _irregular("probe budget exceeded (pair sweep)")
    base = baselines[0]
    for l1 in range(W):
        d1 = 1 << bits[l1]
        for l2 in range(l1 + 1, W):
            d2 = 1 << bits[l2]
            blk = np.tile(base, (d1 * d2, 1))
            v1 = np.repeat(np.arange(d1, dtype=np.uint32), d2)
            v2 = np.tile(np.arange(d2, dtype=np.uint32), d1)
            blk[:, l1] = v1
            blk[:, l2] = v2
            grid = _outputs(*probe(blk)).reshape(d1, d2, F, W + 1)
            # Exact conditional dependence on this grid: l1 drives an
            # output iff the output varies along the l1 axis at some
            # fixed l2 value (and vice versa) — attributing by "some
            # both-deviated row moved" would smear every dependence
            # onto its sweep partner.
            hit1 = (grid != grid[:1]).any(axis=(0, 1))  # [F, W+1]
            hit2 = (grid != grid[:, :1]).any(axis=(0, 1))
            for lane, hit in ((l1, hit1), (l2, hit2)):
                for a, o in zip(*np.nonzero(hit)):
                    deps[int(a)][int(o)].add(lane)

    # Tabulate by key set: enumerate each group's joint domain at
    # independent verification contexts; a context disagreement means
    # the sweep missed a key lane — refine and retry.
    worklist = {}
    for a in range(F):
        for o in range(W + 1):
            worklist.setdefault(tuple(sorted(deps[a][o])),
                                []).append((a, o))
    groups: List[_Group] = []
    consts: List[Tuple[int, int, int]] = []
    copies = 0
    refines: dict = {}
    pending = sorted(worklist.items())
    while pending:
        keys, cols = pending.pop(0)
        domain = 1
        for k in keys:
            domain *= 1 << bits[k]
        if domain > KEY_DOMAIN_CAP:
            a, o = cols[0]
            what = "valid" if o == W else f"lane {o}"
            return _irregular(
                f"key domain too large (action {a}, {what}: "
                f"{domain} > {KEY_DOMAIN_CAP})")
        nonkey = [l for l in range(W) if l not in keys]
        sweep_n = sum(1 << bits[l] for l in nonkey)
        if domain * (_N_VERIFY + sweep_n) > _GROUP_PROBE_CAP:
            a, o = cols[0]
            what = "valid" if o == W else f"lane {o}"
            return _irregular(
                f"probe budget exceeded (action {a}, {what})")
        ctxs = _spread_contexts(rng, bits, _N_VERIFY)
        assigns = np.array(
            list(itertools.product(*((range(1 << bits[k]))
                                     for k in keys))),
            dtype=np.uint32).reshape(domain, len(keys))
        # Block A: the full joint key domain at every spread context
        # (cross-context agreement = "nothing outside the keys moved
        # the output" at those points). Block B, the closure sweep:
        # every non-key lane over its FULL domain at context 0, at
        # every key assignment — a residual single-lane dependence is
        # caught deterministically, not with 1 - 1/D probability.
        rows_a = np.repeat(ctxs, domain, axis=0)  # [R*D, W]
        for j, k in enumerate(keys):
            rows_a[:, k] = np.tile(assigns[:, j], _N_VERIFY)
        blocks, bmeta = [rows_a], []
        for lane in nonkey:
            d = 1 << bits[lane]
            blk = np.tile(ctxs[0], (d * domain, 1))
            blk[:, lane] = np.repeat(
                np.arange(d, dtype=np.uint32), domain)
            for j, k in enumerate(keys):
                blk[:, k] = np.tile(assigns[:, j], d)
            blocks.append(blk)
            bmeta.append((lane, d))
        out = _outputs(*probe(np.concatenate(blocks, axis=0)))
        out_a = out[:_N_VERIFY * domain].reshape(
            _N_VERIFY, domain, F, W + 1)
        vals = np.stack([out_a[:, :, a, o] for (a, o) in cols],
                        axis=2)  # [R, D, n_cols]
        agree = (vals == vals[:1]).all(axis=0)  # [D, n_cols]
        bad = None  # (column index, offending lane or None)
        if not agree.all():
            d_bad, c_bad = map(int, np.argwhere(~agree)[0])
            a, o = cols[c_bad]
            r_bad = int(np.nonzero(
                vals[:, d_bad, c_bad] != vals[0, d_bad, c_bad])[0][0])
            bad = (c_bad, _find_offender(
                probe, rows_a[d_bad].copy(),
                rows_a[r_bad * domain + d_bad].copy(), keys, a, o))
        else:
            off = _N_VERIFY * domain
            for lane, d in bmeta:
                blk = out[off:off + d * domain].reshape(
                    d, domain, F, W + 1)
                off += d * domain
                for ci, (a, o) in enumerate(cols):
                    if (blk[:, :, a, o]
                            != vals[0][:, ci][None, :]).any():
                        bad = (ci, lane)
                        break
                if bad is not None:
                    break
        if bad is not None:
            c_bad, offender = bad
            a, o = cols[c_bad]
            refines[(a, o)] = refines.get((a, o), 0) + 1
            if offender is None or \
                    refines[(a, o)] > _MAX_REFINE_PER_COL:
                what = "valid" if o == W else f"lane {o}"
                return _irregular(
                    f"inference unstable (action {a}, {what})")
            new_keys = tuple(sorted(keys + (offender,)))
            rest = [c for c in cols if c != (a, o)]
            if rest:
                pending.insert(0, (keys, rest))
            pending.insert(0, (new_keys, [(a, o)]))
            continue
        table_u32 = vals[0]  # [D, n_cols], exact step outputs
        if not keys:
            consts.extend((a, o, int(table_u32[0, j]))
                          for j, (a, o) in enumerate(cols))
            continue
        # Passthrough columns — the table is the identity on the
        # output's own single key lane — ride the broadcast base frame.
        live = []
        if len(keys) == 1:
            ident = assigns[:, 0]
            for j, (a, o) in enumerate(cols):
                if o == keys[0] and o < W and \
                        (table_u32[:, j] == ident).all():
                    copies += 1
                else:
                    live.append(j)
        else:
            live = list(range(len(cols)))
        if not live:
            continue
        cols = [cols[j] for j in live]
        table_u32 = table_u32[:, live]
        strides = []
        s = 1
        for k in reversed(keys):
            strides.append(s)
            s *= 1 << bits[k]
        strides = tuple(reversed(strides))
        table = np.empty((domain, 2 * len(cols)), np.float32)
        table[:, 0::2] = (table_u32 & 0xFFFF).astype(np.float32)
        table[:, 1::2] = (table_u32 >> 16).astype(np.float32)
        groups.append(_Group(tuple(keys), strides, domain, cols, table))

    plan = MatmulPlan(W, F, groups, consts, copies)
    return MatmulClassification(
        True,
        f"regular ({len(groups)} key groups, "
        f"{plan.matmul_ops} macs/row)", plan)


@jax.named_scope("expand")
def matmul_expand(dm, plan: MatmulPlan, vecs, valid, tables=None):
    """Drop-in replacement for ``engine.expand_frontier`` on a regular
    model: same signature, same returns (``succ_flat [B*F, W]``,
    ``valid_flat [B*F]``, ``succ_count``, ``terminal [B]``), same bits
    — successor generation runs as one dense product per key group
    instead of the per-row vmapped ``step``. ``tables`` optionally
    supplies the per-group transition tables as live arrays (one per
    ``plan.groups`` entry, in order) — the megakernels pass them as
    ``pallas_call`` operands, since a kernel may not close over array
    constants; the default materializes each group's host table
    in-trace."""
    if tables is None:
        tables = [jnp.asarray(g.table) for g in plan.groups]
    B = vecs.shape[0]
    F, W = plan.fanout, plan.width
    has_boundary = dm.boundary(
        jnp.zeros((W,), jnp.uint32)) is not None
    # Base frame: every successor starts as a copy of its source row —
    # passthrough lanes are done already; tabulated outputs overwrite.
    succ = jnp.broadcast_to(vecs[:, None, :], (B, F, W))
    sv = jnp.zeros((B, F), jnp.bool_)
    for a, o, val in plan.consts:
        if o == W:
            sv = sv.at[:, a].set(bool(val))
        else:
            succ = succ.at[:, a, o].set(jnp.uint32(val))
    for g, table in zip(plan.groups, tables):
        kidx = jnp.zeros((B,), jnp.int32)
        for k, stride in zip(g.keys, g.strides):
            kidx = kidx + vecs[:, k].astype(jnp.int32) * stride
        # ≥2D iota (Mosaic requires it); one-hot [B, D] × table
        # [D, 2n] is the MXU-shaped product (exact: the one-hot picks
        # one row, every entry < 2^16 is an exact f32 integer).
        iota = jax.lax.broadcasted_iota(jnp.int32, (1, g.domain), 1)
        onehot = (kidx[:, None] == iota).astype(jnp.float32)
        prod = jnp.dot(onehot, table,
                       preferred_element_type=jnp.float32)
        cols = (prod[:, 0::2].astype(jnp.uint32)
                | (prod[:, 1::2].astype(jnp.uint32) << 16))
        for j, (a, o) in enumerate(g.cols):
            if o == W:
                sv = sv.at[:, a].set(cols[:, j] != 0)
            else:
                succ = succ.at[:, a, o].set(cols[:, j])
    sv = sv & valid[:, None]
    if has_boundary:
        sv = sv & jax.vmap(jax.vmap(dm.boundary))(succ)
    succ_count = jnp.sum(sv, dtype=jnp.int64)
    terminal = valid & ~sv.any(axis=1)
    s = sv.size
    return succ.reshape(s, W), sv.reshape(s), succ_count, terminal
