"""Records the small TPU trace that ``test_trace_stages.py`` reads: one
fused check of 2pc with 4 resource managers under the profiler, inside a
``bench.traced`` span, with its dispatch log. Run it on a TPU from the
root of the checkout:

    python tests/benchmark/record_tpu_trace.py [out_dir]

It writes ``tpu_2pc4.xplane.pb`` and ``tpu_2pc4.json`` to ``out_dir``
(default ``tests/benchmark/data``). The trace is cut to what the
benchmark's trace readers use, so that it stays small: the TPU plane's
``XLA Ops`` and ``XLA Modules`` lines, each op's name and its
``tf_op`` and ``program_id`` stats; the host plane's ``fused.*`` and
``bench.*`` spans; and, of the program's HLO, the name-scope path of
each instruction whose op has no ``tf_op``. Nothing else changes: every
kept event keeps its time.
"""

import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import trace_reduce, trace_stages as ts  # noqa: E402

SIZES = {"batch_size": 64, "table_capacity": 1 << 14,
         "arena_capacity": 1 << 14}
KEEP_LINES = ("XLA Ops", "XLA Modules")
KEEP_STATS = (ts.PATH_STAT, ts.PROGRAM_STAT)


def _enc(x: int) -> bytes:
    out = bytearray()
    while True:
        b = x & 0x7F
        x >>= 7
        if x:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _ld(num: int, payload: bytes) -> bytes:
    return _enc(num << 3 | 2) + _enc(len(payload)) + payload


def _raw(buf):
    """``(field number, value or None, the field's bytes)``."""
    i = 0
    while i < len(buf):
        start = i
        key, i = ts._varint(buf, i)
        num, wire = key >> 3, key & 7
        value = None
        if wire == 0:
            _, i = ts._varint(buf, i)
        elif wire == 2:
            size, i = ts._varint(buf, i)
            value = buf[i:i + size]
            i += size
        else:
            i += 8 if wire == 1 else 4
        yield num, value, bytes(buf[start:i])


def _stat_ids(plane, names) -> set:
    return {ts._first(e, 1, 0) for n, e in ts._fields(plane) if n == 5
            and ts._text(ts._first(ts._first(e, 2, b""), 2, b"")) in names}


def _event(ev) -> bytes:
    # metadata id, offset, duration, occurrences; no per-event stats
    return b"".join(raw for n, _, raw in _raw(ev) if n in (1, 2, 3, 5))


def _metadata_entry(entry, keep_stats) -> bytes:
    """One event-metadata map entry, its stats cut to ``keep_stats``."""
    meta = ts._first(entry, 2, b"")
    kept = b"".join(
        raw for n, value, raw in _raw(meta)
        if n in (1, 2, 4) or (n == 5 and ts._first(value, 1) in keep_stats))
    key = b"".join(raw for n, _, raw in _raw(entry) if n == 1)
    return _ld(4, key + _ld(2, kept))


def _device_plane(plane) -> tuple:
    keep = _stat_ids(plane, KEEP_STATS)
    path_id = _stat_ids(plane, (ts.PATH_STAT,))
    out, untagged = [], set()
    for n, value, raw in _raw(plane):
        if n in (1, 2, 5):
            out.append(raw)
        elif n == 4:
            out.append(_metadata_entry(value, keep))
            meta = ts._first(value, 2, b"")
            if not any(m == 5 and ts._first(s, 1) in path_id
                       for m, s in ts._fields(meta)):
                untagged.add(ts._text(ts._first(meta, 4, b"")))
        elif n == 3 and ts._text(ts._first(value, 2, b"")) in KEEP_LINES:
            out.append(_ld(3, b"".join(
                _ld(4, _event(v)) if m == 4 else r
                for m, v, r in _raw(value))))
    return b"".join(out), untagged


def _host_plane(plane) -> bytes:
    names = {ts._first(e, 1, 0): ts._text(ts._first(ts._first(e, 2, b""),
                                                    2, b""))
             for n, e in ts._fields(plane) if n == 4}
    spans = {i for i, name in names.items()
             if name.startswith((ts.PROGRAM_SPAN, trace_reduce.SPAN_PREFIX))}
    out = []
    for n, value, raw in _raw(plane):
        if n in (1, 2):
            out.append(raw)
        elif n == 4 and ts._first(ts._first(value, 2, b""), 1) in spans:
            out.append(_metadata_entry(value, set()))
        elif n == 3:
            events = [_ld(4, _event(v)) for m, v, _ in _raw(value)
                      if m == 4 and ts._first(v, 1) in spans]
            if events:
                out.append(_ld(3, b"".join(
                    r for m, _, r in _raw(value) if m != 4) + b"".join(
                        events)))
    return b"".join(out)


def _hlo_plane(plane, untagged) -> bytes:
    """The metadata plane with each program's HLO cut to the paths of
    the instructions named in ``untagged``."""
    hlo_id = _stat_ids(plane, (ts.HLO_PROTO_STAT,))
    out = []
    for n, value, raw in _raw(plane):
        if n in (2, 5):
            out.append(raw)
        elif n == 4:
            meta = ts._first(value, 2, b"")
            fields = []
            for m, v, r in _raw(meta):
                if m == 5 and ts._first(v, 1) in hlo_id:
                    paths = ts._instruction_paths(ts._first(v, 6, b""))
                    instrs = b"".join(
                        _ld(2, _ld(1, name.encode())
                            + _ld(7, _ld(2, path.encode())))
                        for name, path in paths.items() if name in untagged)
                    hlo = _ld(1, _ld(3, instrs))
                    fields.append(_ld(5, b"".join(
                        r2 for m2, _, r2 in _raw(v) if m2 == 1)
                        + _ld(6, hlo)))
                elif m != 5:
                    fields.append(r)
            out.append(_ld(4, b"".join(r for m, _, r in _raw(value)
                                       if m == 1)
                           + _ld(2, b"".join(fields))))
    return b"".join(out)


def cut(path: str) -> bytes:
    """The trace at ``path`` cut to what the readers use."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    planes = {ts._text(ts._first(p, 2, b"")): p
              for n, p in ts._fields(space) if n == 1}
    device, untagged = _device_plane(planes["/device:TPU:0"])
    host = next(p for name, p in planes.items()
                if name.startswith(trace_reduce.HOST_PLANE))
    return (_ld(1, device) + _ld(1, _host_plane(host))
            + _ld(1, _hlo_plane(planes["/host:metadata"], untagged)))


def main(out_dir: str) -> None:
    import jax

    from stateright_tpu.jit_cache import WaveProgramCache
    from stateright_tpu.service.registry import default_registry

    reg = default_registry()
    model, params = reg.build("twopc", {"rm_count": 4})
    key = reg.program_key("twopc", params)
    cache = WaveProgramCache()

    def check():
        return model.checker().spawn_tpu_bfs(
            fused=True, program_cache=cache, program_key=key,
            **SIZES).join()

    check()  # compiles
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
        checker = check()
    jax.profiler.stop_trace()
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "tpu_2pc4.xplane.pb"), "wb") as f:
        f.write(cut(trace_reduce.newest_xplane(tmp)))
    shutil.rmtree(tmp)
    log = [{k: e[k] for k in ("waves", "rows", "candidates", "novel",
                              "probe_rounds", "dedup_rounds", "host_s")}
           for e in checker.dispatch_log]
    with open(os.path.join(out_dir, "tpu_2pc4.json"), "w") as f:
        json.dump({"sizes": SIZES, "device": jax.devices()[0].device_kind,
                   "dispatch_log": log}, f, indent=1)


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else
         os.path.join(os.path.dirname(os.path.abspath(__file__)), "data"))
