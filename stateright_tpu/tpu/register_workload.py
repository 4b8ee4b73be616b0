"""Reusable device compilation of register workloads under linearizability.

Every storage example in the reference follows one shape
(`actor/register.rs:119-217`): ``S`` servers behind the ``RegisterMsg``
Put/Get interface, ``C`` clients that each Put one value then Get
(round-robin destinations), and a ``LinearizabilityTester`` riding along
as ActorModel history. Round 1 hand-wrote this once, inside the paxos
device model; this module factors the workload-generic pieces so a new
register protocol gets a device form by implementing only its *server*:

- :class:`RegisterWorkloadDevice` — an ``ActorDeviceModel`` base that
  owns the envelope bit layout, the client state machine + history
  recording (`register.rs:174-217`, `register.rs:37-88`), the
  client/history/network host codec, and the two standard properties
  (``linearizable`` on device, ``value chosen``).
- :func:`serialization_tables` + the on-device linearizability predicate
  — the reference's per-state backtracking search
  (`linearizability.rs:178-240`) re-expressed as a static enumeration of
  all per-thread-ordered interleavings (a data-parallel reduction over
  multiset permutations, with all position reasoning precomputed into
  constant tables), valid for the "Put then Get per client" history
  universe.

Envelope bit layout (model-specific fields from bit 15 up):

====  ========  ========================================
bits  field     meaning
====  ========  ========================================
0:3   dst       destination actor index
3:6   src       source actor index
6:10  kind      PUT/GET/PUTOK/GETOK then internal kinds
10:13 req       request id as ``(op-1) << 2 | client``
13:15 value     0 = NO_VALUE else 1 + client index
====  ========  ========================================

Subclass contract: ``SERVER_LANES`` (lane names per server),
``server_deliver(lanes, f) -> (new_lanes, handled, outs)`` (the
delivery's effect on the ``f.dst`` server's pre-gathered lane vector —
the base class gathers it, scatters the result back, and assembles the
body), ``encode_server``/``decode_server`` (host codec), and — if the
protocol has internal messages — ``INTERNAL_KINDS`` +
``encode_internal`` / ``decode_internal``.
"""

from __future__ import annotations

from functools import lru_cache
from itertools import permutations

import numpy as np

import jax
import jax.numpy as jnp

from .actor_device import EMPTY_ENV, ActorDeviceModel

__all__ = ["RegisterWorkloadDevice", "perm_tables",
           "serialization_tables", "PUT", "GET", "PUTOK", "GETOK"]

PUT, GET, PUTOK, GETOK = range(4)

NO_VALUE = "\x00"


@lru_cache(maxsize=None)
def perm_tables(c: int):
    """Static serialization tables for the linearizability reduction: all
    multiset permutations of (thread 0 ×2, ..., thread c-1 ×2), each op's
    occurrence index, and the position of each (thread, op) slot."""
    seen = set()
    perms = []
    for p in permutations([t for t in range(c) for _ in range(2)]):
        if p not in seen:
            seen.add(p)
            perms.append(p)
    perms.sort()
    nc = len(perms)
    thread = np.array(perms, np.int32)                    # [NC, 2c]
    occ = np.zeros_like(thread)
    pos = np.zeros((nc, c, 2), np.int32)
    for i, p in enumerate(perms):
        counts = [0] * c
        for j, t in enumerate(p):
            occ[i, j] = counts[t]
            pos[i, t, counts[t]] = j
            counts[t] += 1
    return thread, occ, pos


@lru_cache(maxsize=None)
def observation_tables(c: int):
    """Constant tables for the gather-form serialization predicate.

    The combo axis is (inclusion mask x permutation), but a state only
    influences a combo through three *tiny* integers per thread —
    which writers are placed (a c-bit set), the thread's read return,
    and its happened-before edges (2 bits per peer) — so everything
    else collapses into lookup tables:

    - ``obs[perm, t, placed_set]``: the value thread t's read observes
      (0 = none): the placed writer with the greatest position before
      the read.
    - ``edge_ok[perm, t, hb]``: no op recorded as completed before t's
      read sits after it in this permutation
      (`linearizability.rs:198-227`).

    The runtime predicate is 2^c * c gathers of [n_perms] vectors from
    these tables — ~5x fewer (and far smaller) device ops than the
    flattened-combo reduction of :func:`serialization_tables`, which is
    kept for the differential test.
    """
    _, _, pos = perm_tables(c)
    nc = pos.shape[0]
    obs = np.zeros((nc, c, 1 << c), np.uint32)
    edge_ok = np.zeros((nc, c, 1 << (2 * c)), bool)
    for perm in range(nc):
        for t in range(c):
            p_read = pos[perm, t, 1]
            for placed in range(1 << c):
                best_pos, v = -1, 0
                for j in range(c):
                    pw = pos[perm, j, 0]
                    if (placed >> j) & 1 and pw < p_read and pw > best_pos:
                        best_pos, v = pw, j + 1
                obs[perm, t, placed] = v
            for hb in range(1 << (2 * c)):
                ok = True
                for j in range(c):
                    if j == t:
                        continue
                    edge = (hb >> (2 * j)) & 3
                    if ((edge >= 1 and pos[perm, j, 0] > p_read)
                            or (edge >= 2 and pos[perm, j, 1] > p_read)):
                        ok = False
                        break
                edge_ok[perm, t, hb] = ok
    return obs, edge_ok


@lru_cache(maxsize=None)
def packed_observation_tables(c: int):
    """Bit-packed (over the permutation axis) observation tables.

    At 4 clients the gather-form predicate moves 8 rows of 2,520 bools
    per (state, mask) — 283 us/state staged on the CPU backend, 144x
    the 3-client cost. Packing the permutation axis into uint64 words
    turns each constraint into one [n_words] gather + AND (n_words =
    ceil(n_perms/64): 40 at C=4, 2 at C=3), ~64x less data movement
    than the bool rows with identical semantics:

    - ``ok_v[t, placed * (c+1) + ret]``: bit p set iff thread t's read
      observes ``ret`` under permutation p with writer set ``placed``.
    - ``edge_pk[t, hb]``: bit p set iff no happened-before edge of
      thread t's read is violated by permutation p.

    Pad bits (beyond n_perms) are zero, so they never make ``any``
    true; rows for inactive constraints are all-ones and drop out of
    the AND.
    """
    obs, edge_ok = observation_tables(c)
    nc = obs.shape[0]
    # uint64 words (requires the engines' x64 mode, which the u64
    # fingerprints already force): half the gather traffic of u32 —
    # the row size is what the C=4 predicate cost scales with.
    nw = (nc + 63) // 64
    word = np.arange(nc) // 64
    bit = np.uint64(1) << (np.arange(nc) % 64).astype(np.uint64)

    def pack(bools):  # [NC] -> [nw]
        out = np.zeros(nw, np.uint64)
        np.bitwise_or.at(out, word[bools], bit[bools])
        return out

    ok_v = np.zeros((c, (1 << c) * (c + 1), nw), np.uint64)
    for t in range(c):
        for placed in range(1 << c):
            for ret in range(c + 1):
                ok_v[t, placed * (c + 1) + ret] = \
                    pack(obs[:, t, placed] == ret)
    edge_pk = np.zeros((c, 1 << (2 * c), nw), np.uint64)
    for t in range(c):
        for hb in range(1 << (2 * c)):
            edge_pk[t, hb] = pack(edge_ok[:, t, hb])
    return ok_v, edge_pk


@lru_cache(maxsize=None)
def serialization_tables(c: int):
    """Static tables for the *restructured* linearizability reduction.

    Instead of walking each permutation sequentially (simulating the
    register op by op), the predicate only needs, for every
    (inclusion-mask, permutation) combo and every reading thread ``t``:

    - which writer threads sit before ``t``'s read, in descending
      position order (the first *placed* one is the value the read
      observes) — ``wbefore[i, t, slot]`` with ``c`` meaning "none";
    - whether peer ``j``'s first/second op sits *after* ``t``'s read
      (``later0/later1[i, t, j]``) — a real-time-edge violation when the
      state's recorded happened-before edge says it completed earlier.

    Everything is independent of the state, so it collapses to constant
    gather tables over one flattened combo axis ``P = 2^c * NC``; the
    runtime predicate is ~10x fewer (and fully fusible) device ops than
    the sequential walk.
    """
    _, _, pos = perm_tables(c)
    nc = pos.shape[0]
    p_total = (1 << c) * nc
    include = np.zeros((p_total, c), bool)
    wbefore = np.zeros((p_total, c, c), np.int32)
    later0 = np.zeros((p_total, c, c), bool)
    later1 = np.zeros((p_total, c, c), bool)
    for mask in range(1 << c):
        for perm in range(nc):
            i = mask * nc + perm
            for t in range(c):
                include[i, t] = bool((mask >> t) & 1)
                p_read = pos[perm, t, 1]
                writers = sorted(
                    (j for j in range(c) if pos[perm, j, 0] < p_read),
                    key=lambda j: -pos[perm, j, 0])
                for slot in range(c):
                    wbefore[i, t, slot] = (writers[slot]
                                           if slot < len(writers) else c)
                for j in range(c):
                    later0[i, t, j] = pos[perm, j, 0] > p_read
                    later1[i, t, j] = pos[perm, j, 1] > p_read
    return include, wbefore, later0, later1


class _EnvFields:
    """Decoded common envelope fields (traced scalars). The value field
    is 2 bits for <= 3 clients (the historical layout) and 3 bits for 4,
    so ``dm`` supplies the layout."""

    __slots__ = ("env", "dst", "src", "kind", "req", "value", "extra")

    def __init__(self, env, dm):
        self.env = env
        self.dst = env & 7
        self.src = (env >> 3) & 7
        self.kind = (env >> 6) & 15
        self.req = (env >> 10) & 7
        self.value = (env >> 13) & dm.value_mask
        self.extra = env >> dm.extra_shift


class RegisterWorkloadDevice(ActorDeviceModel):
    """Base device model for S-servers / C-clients register workloads."""

    #: lane names for one server's state (subclass)
    SERVER_LANES: tuple = ()
    #: names of internal message kinds, assigned codes 4, 5, ... (subclass)
    INTERNAL_KINDS: tuple = ()

    max_out = 1

    def __init__(self, client_count: int, server_count: int, host_cfg,
                 net_slots: int = 0, duplicating: bool = False,
                 lossy: bool = False):
        from .device_model import DeviceFormUnavailable

        if not 1 <= client_count <= 4:
            # The real wall: the req field encodes the client in 2 bits
            # ((op-1)<<2 | client, register.rs:169-196 request-id
            # universe), and 5 clients would unroll 113,400 permutations
            # x 32 in-flight masks into the linearizability reduction.
            # spawn_tpu_bfs catches this and falls back to the host
            # engines, whose LinearizabilityTester + native C++ search
            # have no client bound.
            raise DeviceFormUnavailable(
                "the device envelope encoding and the statically "
                "enumerated linearizability interleavings are sized for "
                "<= 4 clients; larger workloads run on the host engines")
        if server_count > 7 or server_count + client_count > 8:
            raise DeviceFormUnavailable("actor index field is 3 bits")
        if len(self.INTERNAL_KINDS) > 12:
            raise NotImplementedError("kind field is 4 bits (12 internal)")
        self.S = server_count
        self.C = client_count
        # Envelope layout: the value field holds 0..C (0 = NO_VALUE), so
        # 4 clients widen it from the historical 2 bits to 3 and shift
        # the model-specific extra bits up by one.
        self.value_bits = 2 if client_count <= 3 else 3
        self.value_mask = (1 << self.value_bits) - 1
        self.extra_shift = 13 + self.value_bits
        self.host_cfg = host_cfg
        self.duplicating = duplicating
        self.lossy = lossy
        # Fan-out (and so per-wave work) scales with net_slots, so the
        # default tracks measured worst-case occupancy, not a guess: on a
        # non-duplicating network the register workloads peak at ~5
        # in-flight envelopes per client (paxos: 5 @ 1 client, 10 @ 2, 13
        # observed @ 3; ABD/single-copy: 2), so 5C+3 leaves real margin.
        # Broadcast-heavy servers can exceed a per-client bound (one
        # delivery adds up to max_out envelopes), hence the C*(max_out+2)
        # floor — and the engine's overflow lane turns any miss into a
        # hard error naming the fix, never silence. Duplicating networks
        # retain delivered envelopes and need the old generous bound.
        self.net_slots = net_slots or (
            16 * client_count if duplicating
            else max(5 * client_count + 3,
                     client_count * (self.max_out + 2)))
        nsl = len(self.SERVER_LANES)
        self._lane_idx = {n: j for j, n in enumerate(self.SERVER_LANES)}
        self.phase_off = nsl * server_count
        self.hist_off = self.phase_off + client_count
        self.net_offset = self.hist_off + 3 * client_count
        self.state_width = self.net_offset + self.net_slots + 1
        self.error_lane = self.net_offset + self.net_slots
        self._kind_code = {name: 4 + i
                          for i, name in enumerate(self.INTERNAL_KINDS)}

    # -- Packed-row layout (tpu/packing.py) -------------------------------

    def server_lane_bits(self) -> tuple:
        """Bits per server lane, in ``SERVER_LANES`` order (subclass
        hook). The conservative default keeps server lanes unpacked;
        protocols with bounded universes (paxos, ABD, single-copy)
        declare their real widths."""
        return (32,) * len(self.SERVER_LANES)

    def extra_bits(self) -> int:
        """Width of the envelope's model-specific ``extra`` field
        (subclass hook). Without internal kinds nothing writes extra,
        so the default is exact for public-only protocols; protocols
        with internal messages either declare their bound or fall back
        to the full remainder."""
        if not self.INTERNAL_KINDS:
            return 0
        return 32 - self.extra_shift

    def lane_bits(self):
        """The workload-generic packed layout: server lanes from the
        subclass hook, 2-bit client phases, (status, ret, hb) history
        triples, network slots at the real envelope width (+1 bit to
        reserve the all-ones field for ``EMPTY_ENV``), a 1-bit error
        lane. Every bound below mirrors a constant the encoding already
        enforces (the codecs mask by these exact widths)."""
        s_bits = list(self.server_lane_bits())
        env_bits = min(self.extra_shift + self.extra_bits(), 32)
        if env_bits >= 32:
            net_spec = 32
        else:
            net_spec = (env_bits + 1, int(EMPTY_ENV))
        hist = []
        for _ in range(self.C):
            hist += [3,                  # status 0..4
                     self.value_bits,    # get-return value index 0..C
                     2 * self.C]         # hb: 2 bits per peer
        return (s_bits * self.S
                + [2] * self.C           # phases 0..3
                + hist
                + [net_spec] * self.net_slots
                + [1])                   # error/overflow flag lane

    # -- Value universe: 0 = NO_VALUE, 1+k = client k's put value --------

    def value_idx(self, value) -> int:
        if value == NO_VALUE:
            return 0
        return ord(value) - ord("A") + 1

    def value_of(self, idx: int):
        return NO_VALUE if idx == 0 else chr(ord("A") + idx - 1)

    # -- Request ids: request_id = op * actor (`register.rs:169-196`) ----

    def _req_field(self, request_id: int, client_actor: int = None) -> int:
        """``client_actor`` (the Put/Get sender or PutOk/GetOk receiver)
        disambiguates colliding products — e.g. with one server,
        request id 2 is both client 1's op 2 and client 2's op 1."""
        if client_actor is not None:
            op = request_id // client_actor
            if op * client_actor != request_id or op not in (1, 2):
                raise ValueError(
                    f"request id {request_id} not from actor {client_actor}")
            return (op - 1) << 2 | (client_actor - self.S)
        matches = [
            (op, k) for k in range(self.C) for op in (1, 2)
            if op * (self.S + k) == request_id]
        if len(matches) != 1:
            raise ValueError(
                f"request id {request_id} is {'ambiguous' if matches else 'outside the universe'}; "
                "pass the client actor for context")
        op, k = matches[0]
        return (op - 1) << 2 | k

    def _req_id(self, field: int) -> int:
        return ((field >> 2) + 1) * (self.S + (field & 3))

    # -- Envelope codec ---------------------------------------------------

    def build_env(self, *, dst, src, kind, req=0, value=0, extra=0):
        """Device-side envelope construction (all args may be traced)."""
        u = jnp.uint32
        return (u(dst) | u(src) << 3 | u(kind) << 6 | u(req) << 10
                | u(value) << 13 | u(extra) << self.extra_shift)

    def encode_internal(self, inner) -> tuple:
        """Host codec for an ``Internal`` payload → (kind_name, req,
        value, extra). Subclass when INTERNAL_KINDS is nonempty."""
        raise NotImplementedError

    def decode_internal(self, kind_name: str, req: int, value: int,
                        extra: int):
        """Inverse of :meth:`encode_internal`: the inner host message."""
        raise NotImplementedError

    def env_encode(self, envelope) -> int:
        from ..actor.register import Get, GetOk, Internal, Put, PutOk

        msg = envelope.msg
        kind = req = value = extra = 0
        t = type(msg)
        if t is Put:
            kind, req = PUT, self._req_field(msg.request_id,
                                             int(envelope.src))
            value = self.value_idx(msg.value)
        elif t is Get:
            kind, req = GET, self._req_field(msg.request_id,
                                             int(envelope.src))
        elif t is PutOk:
            kind, req = PUTOK, self._req_field(msg.request_id,
                                               int(envelope.dst))
        elif t is GetOk:
            kind, req = GETOK, self._req_field(msg.request_id,
                                               int(envelope.dst))
            value = self.value_idx(msg.value)
        elif t is Internal:
            kind_name, req, value, extra = self.encode_internal(msg.msg)
            kind = self._kind_code[kind_name]
        else:
            raise ValueError(f"unsupported message {msg!r}")
        return (int(envelope.dst) | int(envelope.src) << 3 | kind << 6
                | req << 10 | value << 13 | extra << self.extra_shift)

    def env_decode(self, code: int):
        from ..actor import Id
        from ..actor.model_state import Envelope
        from ..actor.register import Get, GetOk, Internal, Put, PutOk

        dst, src = Id(code & 7), Id((code >> 3) & 7)
        kind = (code >> 6) & 15
        req = (code >> 10) & 7
        value = (code >> 13) & self.value_mask
        extra = code >> self.extra_shift
        if kind == PUT:
            msg = Put(self._req_id(req), self.value_of(value))
        elif kind == GET:
            msg = Get(self._req_id(req))
        elif kind == PUTOK:
            msg = PutOk(self._req_id(req))
        elif kind == GETOK:
            msg = GetOk(self._req_id(req), self.value_of(value))
        else:
            name = self.INTERNAL_KINDS[kind - 4]
            msg = Internal(self.decode_internal(name, req, value, extra))
        return Envelope(src, dst, msg)

    # -- Server lane helpers ----------------------------------------------

    # Both helpers select over the S static server rows instead of
    # indexing at a traced offset: under ``vmap`` a dynamic slice or
    # update becomes a batched gather / scatter, which the TPU compiler
    # lowers to a serial loop over the successor rows. S <= 7 (the
    # 3-bit actor field), so the unrolled select stays small.

    def gather_server(self, vec, dst):
        """All lanes of the (traced) ``dst`` server: ``uint32[n_lanes]``.
        A client ``dst`` clips to server S-1; callers select the client
        branch away via ``is_server``."""
        nsl = len(self.SERVER_LANES)
        rows = vec[:self.S * nsl].reshape(self.S, nsl)
        d = jnp.clip(dst, 0, self.S - 1)
        lanes = rows[self.S - 1]
        for i in range(self.S - 1):
            lanes = jnp.where(d == i, rows[i], lanes)
        return lanes

    def lane(self, lanes, name: str):
        return lanes[self._lane_idx[name]]

    def with_lane(self, lanes, name: str, value):
        return lanes.at[self._lane_idx[name]].set(jnp.uint32(value))

    def scatter_server(self, vec, dst, lanes):
        """Writes a server's lanes back at (traced) index ``dst`` (clipped
        like :meth:`gather_server`; the caller discards the client case).
        ``vec`` is the ``[S * n_lanes]`` server block of a state."""
        nsl = len(self.SERVER_LANES)
        d = jnp.clip(dst, 0, self.S - 1)
        hit = jnp.arange(self.S, dtype=d.dtype)[:, None] == d
        return jnp.where(hit, lanes[None, :],
                         vec.reshape(self.S, nsl)).reshape(self.S * nsl)

    # -- Subclass surface -------------------------------------------------

    def server_deliver(self, lanes, f: _EnvFields):
        """Applies one delivery to the (traced) ``f.dst`` server, whose
        pre-gathered lane vector is ``lanes: uint32[n_lanes]``. Returns
        ``(new_lanes, handled, outs)`` — the updated lane vector (NOT
        scattered back; the base class installs it) and
        ``outs: uint32[max_out]``."""
        raise NotImplementedError

    def encode_server(self, server_state, vec: np.ndarray,
                      base: int) -> None:
        """Host → lanes for one server (``server_state`` is the *inner*
        state, unwrapped from ``RegisterServerState``)."""
        raise NotImplementedError

    def decode_server(self, vec: np.ndarray, base: int, server_index: int):
        """Lanes → inner host server state."""
        raise NotImplementedError

    # -- Deliver dispatch -------------------------------------------------

    def deliver(self, body, env):
        """Component-wise dispatch: the server branch updates only the
        ``f.dst`` server's lanes, the client branch only the phase and
        history components; the body is reassembled with one concatenate
        (full-width ``.at`` chains were the expand stage's dominant cost,
        see the actor_device module docstring)."""
        f = _EnvFields(env, self)
        is_server = f.dst < self.S
        lanes0 = self.gather_server(body, f.dst)
        srv_lanes, srv_handled, srv_outs = self.server_deliver(lanes0, f)
        (cli_phases, cli_hist, cli_handled,
         cli_outs) = self._client_deliver(body, f)
        servers = body[:self.phase_off]
        phases = body[self.phase_off:self.hist_off]
        hist = body[self.hist_off:self.net_offset]
        # Client deliveries scatter the *original* lanes back: a no-op.
        new_servers = self.scatter_server(
            servers, f.dst, jnp.where(is_server, srv_lanes, lanes0))
        new_body = jnp.concatenate([
            new_servers,
            jnp.where(is_server, phases, cli_phases),
            jnp.where(is_server, hist, cli_hist)])
        return (new_body,
                jnp.where(is_server, srv_handled, cli_handled),
                jnp.where(is_server, srv_outs, cli_outs))

    def _client_deliver(self, body, f: _EnvFields):
        """The round-robin Put-then-Get client (`register.rs:174-217`)
        plus history recording (`register.rs:37-88`): PutOk completes the
        Write and invokes the Read (recording happened-before edges over
        peers' completed ops); GetOk completes the Read with its value.
        Returns ``(new_phases [C], new_hist [3C], handled, outs)``."""
        s, c = self.S, self.C
        u = jnp.uint32
        k = f.dst - s  # client index (underflows for servers; masked off)
        phases = body[self.phase_off:self.hist_off]                  # [c]
        histm = body[self.hist_off:self.net_offset].reshape(c, 3)
        status, rets, hbs = histm[:, 0], histm[:, 1], histm[:, 2]
        phase = phases[jnp.clip(k, 0, c - 1)]
        req_op = (f.req >> 2) + 1
        req_k = f.req & 3
        req_matches = (req_k == k) & (req_op == phase)

        putok_case = (f.kind == PUTOK) & (phase == 1) & req_matches
        getok_case = (f.kind == GETOK) & (phase == 2) & req_matches
        handled = putok_case | getok_case

        is_k = jnp.arange(c, dtype=u) == k                      # [c] bool
        new_phase = jnp.where(putok_case, u(2),
                              jnp.where(getok_case, u(3), phase))
        new_phases = jnp.where(is_k, new_phase, phases)

        # Happened-before edges at Read invoke: the number of completed
        # ops per peer, (len-1)+1 encoded, 2 bits per peer.
        comp = jnp.where(status >= 4, u(2),
                         jnp.where(status >= 2, u(1), u(0)))         # [c]
        hb = jnp.sum(jnp.where(is_k, u(0), comp)
                     << (2 * jnp.arange(c, dtype=u)), dtype=u)
        new_status = jnp.where(
            is_k & putok_case, u(3),  # write done + read in flight
            jnp.where(is_k & getok_case, u(4), status))
        new_rets = jnp.where(is_k & getok_case, f.value, rets)
        new_hbs = jnp.where(is_k & putok_case, hb, hbs)
        new_hist = jnp.stack(
            [new_status, new_rets, new_hbs], axis=1).reshape(3 * c)

        # After PutOk the client Gets from server (actor + op_count) % S
        # (`register.rs:184-196` round-robin with op_count = 1).
        get_out = self.build_env(
            dst=(f.dst + 1) % s, src=f.dst, kind=GET,
            req=(u(1) << 2) | jnp.clip(k, 0, 3).astype(u))
        outs = jnp.full((self.max_out,), EMPTY_ENV, u)
        outs = outs.at[0].set(
            jnp.where(putok_case, get_out, u(EMPTY_ENV)))
        return new_phases, new_hist, handled, outs

    # -- Client-symmetry representative -----------------------------------
    #
    # The only sound client exchangeability for register workloads: the
    # scripted client's destinations are index-derived — Put to
    # ``index % server_count`` and op o to ``(index + o - 1) %
    # server_count`` (`register.rs:169-196`) — so exchanging clients
    # whose indices differ mod S would reroute their messages to
    # different servers and is NOT an automorphism. Clients in the same
    # residue class mod S run bit-identical scripts modulo id-derived
    # payloads (request ids ``op * index``, values ``'A' + k``, history
    # thread keys), so the symmetry group is the product of symmetric
    # groups over the residue classes; the representative takes the
    # lexicographically-minimal encoded vector over that group, with
    # every id-derived payload rewritten. At 3 servers the group is
    # trivial below 4 clients and exactly {id, swap(client 0, client 3)}
    # at 4 — the reduction driver config 5 ("paxos check 4 + symmetry")
    # exercises. No reference pin exists (the reference's paxos example
    # has no symmetry arm); the orbit counts are pinned in MEASUREMENTS.

    def client_permutations(self) -> list:
        """Non-identity client permutations (as ``sigma`` tuples mapping
        old client index -> new) preserving the destination pattern."""
        from itertools import permutations as iperms, product

        cached = getattr(self, "_sym_perms", None)
        if cached is not None:
            return cached
        classes: dict = {}
        for k in range(self.C):
            classes.setdefault(k % self.S, []).append(k)
        per_class = []
        for members in classes.values():
            per_class.append([dict(zip(members, p))
                              for p in iperms(members)])
        identity = tuple(range(self.C))
        sigmas = []
        for combo in product(*per_class):
            sigma = list(range(self.C))
            for mapping in combo:
                for old, new in mapping.items():
                    sigma[old] = new
            if tuple(sigma) != identity:
                sigmas.append(tuple(sigma))
        self._sym_perms = sigmas
        return sigmas

    def sym_extra_tables(self, sigma: tuple, t: dict) -> None:
        """Hook: add model-specific rewrite tables for ``sigma`` to ``t``
        (e.g. proposal/accepted-pair index maps). Default: none."""

    def sym_rewrite_servers(self, servers, t: dict, xp):
        """Hook: rewrite id-derived payloads inside the ``[S, n_lanes]``
        server lanes under the client permutation ``t``. Raises by
        default — an identity default would silently merge inequivalent
        states for any server that stores client-derived data."""
        raise NotImplementedError(
            f"{type(self).__name__} does not implement client-symmetry "
            "server rewriting (sym_rewrite_servers)")

    def sym_rewrite_extra(self, kind, extra, t: dict, xp):
        """Hook: rewrite the internal-message ``extra`` bits (vectorized
        over network slots) under ``t``. Default: identity when the
        protocol has no internal kinds; otherwise raises for the same
        reason as :meth:`sym_rewrite_servers`."""
        if not self.INTERNAL_KINDS:
            return extra
        raise NotImplementedError(
            f"{type(self).__name__} does not implement client-symmetry "
            "extra-bit rewriting (sym_rewrite_extra)")

    def sym_rewrite_internal_req(self, kind, req, t: dict, xp):
        """Hook: rewrite the ``req`` field of *internal* kinds under
        ``t`` (public Put/Get/PutOk/GetOk reqs are always client-derived
        and map generically). Identity when there are no internal kinds;
        otherwise the model must choose — e.g. paxos internals leave req
        unused (identity), ABD internals carry real request ids
        (``t["req"]`` map)."""
        if not self.INTERNAL_KINDS:
            return req
        raise NotImplementedError(
            f"{type(self).__name__} does not implement client-symmetry "
            "internal-req rewriting (sym_rewrite_internal_req)")

    def _sym_tables(self) -> list:
        """Per-permutation rewrite tables. Table sizes cover the full
        field ranges (not just the reachable universe) because the
        device path maps garbage rows of invalid successors too — jnp
        gathers clamp, but the tables stay total to keep the numpy host
        path identical."""
        cached = getattr(self, "_sym_tables_cache", None)
        if cached is not None:
            return cached
        c = self.C
        tables = []
        for sigma in self.client_permutations():
            val = np.arange(self.value_mask + 1, dtype=np.uint32)
            for k in range(c):
                val[1 + k] = 1 + sigma[k]
            req = np.arange(8, dtype=np.uint32)
            for r in range(8):
                op_bit, k = r >> 2, r & 3
                if k < c:
                    req[r] = (op_bit << 2) | sigma[k]
            actor = np.arange(8, dtype=np.uint32)
            for k in range(c):
                actor[self.S + k] = self.S + sigma[k]
            inv = np.argsort(np.asarray(sigma))
            t = {"sigma": sigma, "inv": inv, "val": val, "req": req,
                 "actor": actor}
            self.sym_extra_tables(sigma, t)
            tables.append(t)
        self._sym_tables_cache = tables
        return tables

    def _sym_rewrite(self, vec, t: dict, xp):
        """Applies one client permutation to an encoded state —
        ``xp``-generic (jnp on device, np on the host DFS path)."""
        s, c, e = self.S, self.C, self.net_slots
        nsl = len(self.SERVER_LANES)
        servers = vec[:self.phase_off].reshape(s, nsl)
        phases = vec[self.phase_off:self.hist_off]
        hist = vec[self.hist_off:self.net_offset].reshape(c, 3)
        net = vec[self.net_offset:self.net_offset + e]
        tail = vec[self.net_offset + e:]

        inv = t["inv"]  # static numpy: new row j takes old row inv[j]
        val_map = xp.asarray(t["val"])
        req_map = xp.asarray(t["req"])
        actor_map = xp.asarray(t["actor"])

        new_servers = self.sym_rewrite_servers(servers, t, xp)
        new_phases = phases[inv]
        status = hist[inv, 0]
        rets = val_map[xp.minimum(hist[inv, 1], self.value_mask)]
        hb_old = hist[inv, 2]
        hb_new = xp.zeros_like(hb_old)
        for j in range(c):  # new peer j == old peer inv[j]
            hb_new = hb_new | (((hb_old >> (2 * int(inv[j]))) & 3)
                               << (2 * j))
        new_hist = xp.stack([status, rets, hb_new], axis=1)

        dst = net & 7
        src = (net >> 3) & 7
        kind = (net >> 6) & 15
        req = (net >> 10) & 7
        value = (net >> 13) & self.value_mask
        extra = net >> self.extra_shift
        new_extra = self.sym_rewrite_extra(kind, extra, t, xp)
        new_req = xp.where(kind < 4, req_map[req],
                           self.sym_rewrite_internal_req(kind, req, t, xp))
        new_env = (actor_map[dst] | actor_map[src] << 3 | kind << 6
                   | new_req << 10 | val_map[value] << 13
                   | new_extra << self.extra_shift).astype(np.uint32)
        # EMPTY maps to itself by construction (all fields identity at
        # their masks' top values), but garbage extras could perturb it;
        # guard explicitly, then restore the sorted canonical slot form.
        new_net = xp.sort(xp.where(net == np.uint32(EMPTY_ENV),
                                   net, new_env))
        return xp.concatenate([
            new_servers.reshape(s * nsl), new_phases,
            new_hist.reshape(3 * c), new_net, tail])

    def representative(self, vec):
        """Device canonicalizer: lexicographically-minimal encoding over
        the client-symmetry group (identity when the group is trivial).
        Used for visited-set dedup only; paths keep original-state
        fingerprints (the `dfs.rs:258-267` rule). Returns ``None``
        (symmetry unsupported) when the model lacks the rewrite hooks."""
        best = vec
        try:
            for t in self._sym_tables():
                cand = self._sym_rewrite(vec, t, jnp)
                diff = best != cand
                first = jnp.argmax(diff)
                best_le = ~jnp.any(diff) | (best[first] < cand[first])
                best = jnp.where(best_le, best, cand)
        except NotImplementedError:
            return None
        return best

    def host_representative(self, state):
        """Host canonicalizer for ``CheckerBuilder.symmetry_fn``: the
        same partition as :meth:`representative`, via the shared
        encoding (encode -> lexmin rewrite -> decode)."""
        vec = np.asarray(self.encode(state), np.uint32)
        best = vec
        for t in self._sym_tables():
            cand = np.asarray(self._sym_rewrite(vec, t, np), np.uint32)
            for b, cv in zip(best.tolist(), cand.tolist()):
                if cv != b:
                    if cv < b:
                        best = cand
                    break
        return self.decode(best)

    # -- Host state codec -------------------------------------------------

    def encode(self, state) -> np.ndarray:
        s, c = self.S, self.C
        nsl = len(self.SERVER_LANES)
        vec = np.zeros(self.state_width, np.uint32)
        for i in range(s):
            self.encode_server(state.actor_states[i].state, vec, nsl * i)
        for k in range(c):
            cs = state.actor_states[s + k]
            vec[self.phase_off + k] = (3 if cs.awaiting is None
                                       else cs.op_count)
        self._encode_history(state.history, vec)
        vec[self.net_offset:] = self.encode_network(state.network)
        return vec

    def decode(self, vec: np.ndarray):
        from ..actor.model_state import ActorModelState, Network
        from ..actor.register import (RegisterClientState,
                                      RegisterServerState)

        s, c = self.S, self.C
        nsl = len(self.SERVER_LANES)
        actor_states = []
        for i in range(s):
            actor_states.append(RegisterServerState(
                self.decode_server(vec, nsl * i, i)))
        for k in range(c):
            phase = int(vec[self.phase_off + k])
            i = s + k
            if phase == 3:
                cs = RegisterClientState(awaiting=None, op_count=3)
            else:
                cs = RegisterClientState(awaiting=phase * i, op_count=phase)
            actor_states.append(cs)
        return ActorModelState(
            actor_states=actor_states,
            network=Network(self.decode_network(vec[self.net_offset:])),
            is_timer_set=[],
            history=self._decode_history(vec),
        )

    # -- History codec (status, get-ret, hb-edges per client) -------------

    def _encode_history(self, tester, vec: np.ndarray) -> None:
        from ..actor import Id

        s, c = self.S, self.C
        assert tester.is_valid_history, \
            "register workloads cannot produce invalid histories"
        for k in range(c):
            tid = Id(s + k)
            completed = tester.history_by_thread.get(tid, ())
            inflight = tester.in_flight_by_thread.get(tid)
            if len(completed) == 0:
                status = 1 if inflight is not None else 0
            elif len(completed) == 1:
                status = 3 if inflight is not None else 2
            else:
                status = 4
            ret = 0
            if len(completed) == 2:
                ret = self.value_idx(completed[1][2].value)  # ReadOk
            hb = 0
            read_cs = None
            if status == 3:
                read_cs = inflight[0]
            elif status == 4:
                read_cs = completed[1][0]
            if read_cs is not None:
                for peer_tid, last_idx in read_cs:
                    j = int(peer_tid) - s
                    hb |= (last_idx + 1) << (2 * j)
            base = self.hist_off + 3 * k
            vec[base] = status
            vec[base + 1] = ret
            vec[base + 2] = hb

    def _decode_history(self, vec: np.ndarray):
        from ..actor import Id
        from ..semantics import LinearizabilityTester, Register
        from ..semantics.register import Read, ReadOk, Write, WriteOk

        s, c = self.S, self.C
        tester = LinearizabilityTester(Register(NO_VALUE))
        for k in range(c):
            base = self.hist_off + 3 * k
            status = int(vec[base])
            if status == 0:
                continue
            tid = Id(s + k)
            hb = int(vec[base + 2])
            read_cs = tuple(sorted(
                (Id(s + j), ((hb >> (2 * j)) & 3) - 1)
                for j in range(c) if (hb >> (2 * j)) & 3))
            write_entry = ((), Write(self.value_of(k + 1)), WriteOk())
            tester.history_by_thread[tid] = ()
            if status == 1:
                tester.in_flight_by_thread[tid] = \
                    ((), Write(self.value_of(k + 1)))
            else:
                tester.history_by_thread[tid] = (write_entry,)
            if status == 3:
                tester.in_flight_by_thread[tid] = (read_cs, Read())
            elif status == 4:
                ret = ReadOk(self.value_of(int(vec[base + 1])))
                tester.history_by_thread[tid] = (
                    write_entry, (read_cs, Read(), ret))
        return tester

    # -- Properties -------------------------------------------------------

    def device_properties(self):
        c = self.C
        e = self.net_slots
        off = self.net_offset
        hist_off = self.hist_off
        ok_v_t, edge_pk_t = packed_observation_tables(c)
        ok_v = jnp.asarray(ok_v_t)          # [c, 2^c * (c+1), nw]
        edge_pk = jnp.asarray(edge_pk_t)    # [c, 4^c, nw]
        nw = ok_v.shape[-1]

        value_mask = self.value_mask

        def value_chosen(vec):
            net = vec[off:off + e]
            kind = (net >> 6) & 15
            value = (net >> 13) & value_mask
            return jnp.any((net != EMPTY_ENV) & (kind == GETOK)
                           & (value != 0))

        def serialization_search(vec, real_time_edges: bool):
            """The reference's backtracking searches
            (`linearizability.rs:178-240`,
            `sequential_consistency.rs:151-213`) as a static reduction
            over (inclusion-mask x permutation) combos, bit-packed over
            the permutation axis: a state touches a combo only through
            per-thread small integers (placed-writer set, read return,
            happened-before edges), so each constraint is one gather of
            an [n_words] uint64 row from ``packed_observation_tables``
            ANDed into the per-mask accumulator. The mask axis (2^c) is
            unrolled; dropping the edge constraint yields sequential
            consistency."""
            status = jnp.stack(
                [vec[hist_off + 3 * j] for j in range(c)])          # [c]
            rets = jnp.stack(
                [vec[hist_off + 3 * j + 1] for j in range(c)])
            hbs = jnp.stack(
                [vec[hist_off + 3 * j + 2] for j in range(c)])
            completed_w = jnp.uint32(0)
            inflight_w = jnp.uint32(0)
            for j in range(c):
                completed_w = completed_w | \
                    jnp.where(status[j] >= 2, jnp.uint32(1 << j),
                              jnp.uint32(0))
                inflight_w = inflight_w | \
                    jnp.where(status[j] == 1, jnp.uint32(1 << j),
                              jnp.uint32(0))
            ones = jnp.full((nw,), 0xFFFFFFFFFFFFFFFF, jnp.uint64)
            any_ok = jnp.zeros((), bool)
            for mask in range(1 << c):
                placed = (completed_w
                          | (inflight_w & jnp.uint32(mask))).astype(
                              jnp.int32)                # traced scalar
                acc = ones
                for t in range(c):
                    r_completed = status[t] == 4
                    read_placed = r_completed | \
                        ((status[t] == 3) & bool((mask >> t) & 1))
                    row_v = jax.lax.dynamic_index_in_dim(
                        ok_v[t], placed * (c + 1)
                        + rets[t].astype(jnp.int32),
                        axis=0, keepdims=False)
                    acc = acc & jnp.where(r_completed, row_v, ones)
                    if real_time_edges:
                        row_e = jax.lax.dynamic_index_in_dim(
                            edge_pk[t], hbs[t].astype(jnp.int32),
                            axis=0, keepdims=False)
                        acc = acc & jnp.where(read_placed, row_e, ones)
                any_ok = any_ok | jnp.any(acc != 0)
            return any_ok

        return {
            "linearizable":
                lambda vec: serialization_search(vec, True),
            "sequentially consistent":
                lambda vec: serialization_search(vec, False),
            "value chosen": value_chosen,
            # Same predicate under Eventually expectation (the engines
            # apply ebits semantics from the host property list): the
            # liveness config of BASELINE.json.
            "eventually chosen": value_chosen,
        }
