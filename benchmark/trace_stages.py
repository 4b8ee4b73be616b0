"""Wave and stage device times of the fused checker, and the device's idle
time by program span, from one profiler trace (``.xplane.pb``).

The program names its wave stages with ``jax.named_scope`` (``STAGES``)
and its host loop's turns with profiler spans (``fused.launch``,
``fused.process``, ``fused.stats_wait``, ``fused.grow``,
``fused.checkpoint``, ``fused.parent_sync``). One pass over the trace
gives:

- each device op's stage: the innermost stage name in the op's
  name-scope path. On a TPU the op's event metadata holds the path as
  its ``tf_op`` stat; where it has none (a ``while`` has none), the path
  is read from the HLO of the op's program (its ``program_id`` stat)
  that the trace carries (``/host:metadata``), keyed by instruction
  name;
- the waves' edges: a wave's edge is the start of its one top-level
  ``while`` in scope ``local_dedup``. Every wave runs exactly one such
  loop, and the loop needs all of its wave's fingerprints. The counted
  span runs from the first edge inside the traced stretch (the host span
  ``bench.traced``) to the last, and holds one wave fewer than it has
  edges;
- per wave of the counted span, in ms: the device's busy time (the union
  over all ops) and each stage's (the union of its ops' intervals);
- the stretch's device idle seconds by the program span open at each
  gap's middle: the innermost ``fused.*`` span, else the benchmark's
  ``bench.*`` span, else ``no span``.

With fewer than two edges (a program without the scopes, or a stretch
shorter than a wave) the per-wave numbers are None. Results are cached
by path, so that every metric reader shares the one pass.
"""

from __future__ import annotations

import functools
import os
from collections import defaultdict

from benchmark import trace_reduce

STAGES = ("load", "properties", "expand", "fingerprint", "local_dedup",
          "probe", "store")
EDGE_STAGE = "local_dedup"
PROGRAM_SPAN = "fused."
NO_SPAN = "no span"
#: the op event metadata's stat that holds the op's name-scope path (as
#: ``<path>:<type>``), and the one that names its program
PATH_STAT, PROGRAM_STAT = "tf_op", "program_id"
#: the stat of a ``/host:metadata`` event that holds a program's HLO
HLO_PROTO_STAT = "Hlo Proto"


def stage_of(path: str):
    """The innermost stage name among the path's components, else None."""
    for part in reversed(path.split("/")):
        if part in STAGES:
            return part
    return None


def op_name(event_name: str) -> str:
    """An op event's HLO instruction name: ``%while.93 = (...)`` gives
    ``while.93``."""
    return trace_reduce.short_name(event_name).lstrip("%")


# -- The op metadata the trace carries --------------------------------------
#
# ``jax.profiler.ProfileData`` gives an op event's own stats, but not
# its metadata's, which hold the path. A minimal reader of the protobuf
# wire format reads those, for the few fields it needs: XSpace.planes
# (1); XPlane.name (2), .event_metadata (4, a map of id to
# XEventMetadata), .stat_metadata (5, a map of id to XStatMetadata);
# XEventMetadata.id (1), .name (2), .display_name (4), .stats (5);
# XStat.metadata_id (1), .uint64_value (3), .int64_value (4),
# .str_value (5), .bytes_value (6); XStatMetadata.name (2);
# HloProto.hlo_module (1); HloModuleProto.computations (3);
# HloComputationProto.instructions (2); HloInstructionProto.name (1),
# .metadata (7); OpMetadata.op_name (2).


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _fields(buf):
    """``(field number, value)`` of one message: ints for varints, bytes
    (a memoryview) for length-delimited fields; fixed-width fields are
    skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value = buf[i:i + size]
            i += size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"unsupported wire type {wire}")
        yield num, value


def _first(buf, num, default=None):
    return next((v for n, v in _fields(buf) if n == num), default)


def _text(buf) -> str:
    return bytes(buf).decode(errors="replace")


def _metadata(plane):
    """``(name, display name, {stat name: value})`` of each of a plane's
    event metadata."""
    stat_names = {}
    for n, entry in _fields(plane):
        if n == 5:
            stat_names[_first(entry, 1, 0)] = _text(
                _first(_first(entry, 2, b""), 2, b""))
    for n, entry in _fields(plane):
        if n != 4:
            continue
        meta = _first(entry, 2, b"")
        stats = {}
        for m, stat in _fields(meta):
            if m != 5:
                continue
            key = stat_names.get(_first(stat, 1))
            for f, value in _fields(stat):
                if f in (3, 4):
                    stats[key] = value
                elif f in (5, 6):
                    stats[key] = value if f == 6 else _text(value)
        yield (_text(_first(meta, 2, b"")), _text(_first(meta, 4, b"")),
               stats)


def _instruction_paths(hlo_proto) -> dict:
    """``{instruction name: op_name}`` of one serialized HloProto."""
    names = {}
    for n, comp in _fields(_first(hlo_proto, 1, b"")):
        if n != 3:
            continue
        for m, instr in _fields(comp):
            if m == 2:
                meta = _first(instr, 7)
                if meta is not None:
                    names[_text(_first(instr, 1, b""))] = _text(
                        _first(meta, 2, b""))
    return names


def op_paths(path: str, device_plane: str = trace_reduce.TPU_PLANE) -> dict:
    """``{op event name: name-scope path}`` of the device planes' ops
    (``""`` where the trace gives none)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    planes = [p for n, p in _fields(space) if n == 1]
    ops, programs = {}, {}
    for plane in planes:
        name = _text(_first(plane, 2, b""))
        if name == "/host:metadata":
            for module, _, stats in _metadata(plane):
                hlo = stats.get(HLO_PROTO_STAT)
                if hlo is not None:
                    # the metadata's name is ``<module>(<program id>)``
                    programs[module.rsplit("(", 1)[-1].rstrip(")")] = hlo
        elif name.startswith(device_plane):
            for op, display, stats in _metadata(plane):
                ops[op] = (display or op_name(op), stats)
    out, parsed = {}, {}
    for op, (display, stats) in ops.items():
        tf_op = stats.get(PATH_STAT)
        if tf_op:
            out[op] = tf_op.rsplit(":", 1)[0]
            continue
        program = str(stats.get(PROGRAM_STAT))
        if program in programs and program not in parsed:
            parsed[program] = _instruction_paths(programs[program])
        out[op] = parsed.get(program, {}).get(display, "")
    return out


# -- The pass ----------------------------------------------------------------


def _host_spans(pd, window_span):
    window, spans = None, []
    for plane in pd.planes:
        if not plane.name.startswith(trace_reduce.HOST_PLANE):
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if name == window_span:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                elif (name.startswith(PROGRAM_SPAN)
                      or name.startswith(trace_reduce.SPAN_PREFIX)):
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  name))
    return window, spans


def span_label(spans, t) -> str:
    """The innermost ``fused.*`` span open at ``t``, else the innermost
    ``bench.*`` span, else ``NO_SPAN``."""
    best = {}
    for start, end, name in spans:
        if start <= t <= end:
            kind = name.startswith(PROGRAM_SPAN)
            if kind not in best or start > best[kind][0]:
                best[kind] = (start, name)
    for kind in (True, False):
        if kind in best:
            return best[kind][1]
    return NO_SPAN


def summarize(ops, spans, window) -> dict:
    """The numbers of one device's ops. ``ops`` are ``(start, end, op,
    stage)`` with times in ns, ``spans`` the host spans ``(start, end,
    name)``, ``window`` the traced stretch ``(lo, hi)``."""
    lo, hi = window
    ops = [o for o in ops if o[1] > lo and o[0] < hi]
    loops = [(s, e) for s, e, op, stage in ops
             if stage == EDGE_STAGE and op.startswith("while")]
    edges = [s for s, _ in trace_reduce.union(loops) if lo <= s <= hi]
    busy = trace_reduce.union(trace_reduce.clip(
        [(s, e) for s, e, _, _ in ops], lo, hi))
    points = [lo] + [x for iv in busy for x in iv] + [hi]
    idle = defaultdict(float)
    for i in range(0, len(points), 2):
        a, b = points[i], points[i + 1]
        if b > a:
            idle[span_label(spans, (a + b) / 2)] += (b - a) / 1e9
    out = {"edges": edges, "waves": max(len(edges) - 1, 0),
           "idle_by_span": dict(idle), "wave_device_ms": None,
           "stage_ms": dict.fromkeys(STAGES), "unscoped_ms": []}
    if len(edges) < 2:
        return out
    a, b = edges[0], edges[-1]
    waves = len(edges) - 1
    by_stage, unscoped = defaultdict(list), defaultdict(float)
    for s, e, op, stage in ops:
        if stage is not None:
            by_stage[stage].append((s, e))
        elif e > a and s < b and not op.startswith("while"):
            # a loop's own time is its body's
            unscoped[op] += min(e, b) - max(s, a)

    def per_wave(intervals):
        merged = trace_reduce.union(trace_reduce.clip(intervals, a, b))
        return sum(e - s for s, e in merged) / 1e6 / waves

    out["wave_device_ms"] = per_wave([(s, e) for s, e, _, _ in ops])
    out["stage_ms"] = {n: per_wave(by_stage[n]) for n in STAGES}
    out["unscoped_ms"] = sorted(
        ([n, t / 1e6 / waves] for n, t in unscoped.items()),
        key=lambda x: -x[1])[:10]
    return out


@functools.lru_cache(maxsize=4)
def read(path: str, device_plane: str = trace_reduce.TPU_PLANE,
         op_line: str = trace_reduce.TPU_OP_LINE,
         window_span: str = trace_reduce.WINDOW_SPAN) -> dict:
    """The numbers of one trace file (``summarize``), for the first
    device plane with ops; raises where there is no window span or no
    device op."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    window, spans = _host_spans(pd, window_span)
    if window is None:
        raise ValueError(f"no {window_span!r} span in {path}")
    paths = op_paths(path, device_plane)
    # (op, stage) once per event name: a paxos stretch has millions of
    # events of a few thousand names
    known = {}
    for plane in sorted(pd.planes, key=lambda p: p.name):
        if not plane.name.startswith(device_plane):
            continue
        ops = []
        for ev in trace_reduce._events(plane, op_line):
            name = ev.name
            op = known.get(name)
            if op is None:
                op = known[name] = (op_name(name),
                                    stage_of(paths.get(name, "")))
            start = ev.start_ns
            ops.append((start, start + ev.duration_ns) + op)
        if ops:
            out = summarize(ops, spans, window)
            out["spans"] = [s for s in spans
                            if s[2].startswith(PROGRAM_SPAN)]
            return out
    raise ValueError(f"no device operation in {path}")


def for_run(ctx: dict):
    """The numbers of a ``--trace 1`` run's stretch, else None (the
    stretch never began, or its trace cannot be read)."""
    if not ctx.get("trace"):
        return None
    try:
        path = trace_reduce.newest_xplane(os.path.join(ctx["out_dir"],
                                                       "trace"))
        return read(path)
    except (OSError, ValueError, IndexError):  # IndexError: a cut file
        return None


def per_wave(ctx: dict, key: str, stage: str = None):
    """One per-wave number of the run's trace, else None."""
    res = for_run(ctx)
    if res is None:
        return None
    return res[key] if stage is None else res[key][stage]
