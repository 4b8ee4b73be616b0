"""The shard exchange's yardstick: its device time per wave from a
profiler trace, and the least bytes it has to move between chips.

A mesh check's wave buckets each shard's successors by owner and sends
them home with all-to-alls, all under the program's ``exchange`` name
scope. ``exchange_ms`` is the union of the first chip's ops whose scope
path holds ``exchange``, over ``trace_stages``' counted span (the first
wave edge to the last), per wave.

Whatever the implementation, a successor row that another shard owns
has to cross to it once: its packed state row, its dedup fingerprint,
its path fingerprint, its parent's fingerprint and its property bits
(``EXCHANGE_ROW_EXTRA`` bytes beside the row). Counting only the rows
that carry a successor, over the whole scope's time, makes the share of
the interconnect's peak a lower bound that cannot pass 100% when the
time is real device time.
"""

from __future__ import annotations

import functools
import os

from benchmark import trace_reduce, trace_stages
from benchmark.costs import row_bytes

SCOPE = "exchange"
#: dedup fingerprint, path fingerprint, parent fingerprint (8 B each)
#: and property bits (4 B)
EXCHANGE_ROW_EXTRA = 8 + 8 + 8 + 4


def row_exchange_bytes(row_bits: int) -> int:
    """The least bytes one successor row takes across chips."""
    return row_bytes(row_bits) + EXCHANGE_ROW_EXTRA


def shard_wave_bytes(exchange_rows: int, shards: int, waves: int,
                     row_bits: int) -> float:
    """The least bytes a shard sends to the others in one wave, from
    the rows all shards sent to another over ``waves`` waves."""
    return exchange_rows / shards / waves * row_exchange_bytes(row_bits)


def ici_share(wave_bytes: float, exchange_ms: float,
              ici_bits_per_s: float) -> float:
    """``wave_bytes`` over the exchange's time per wave, as a share of
    one chip's interconnect peak."""
    return wave_bytes / (exchange_ms / 1e3) / (ici_bits_per_s / 8)


def in_scope(path: str) -> bool:
    return SCOPE in path.split("/")


@functools.lru_cache(maxsize=4)
def read(path: str, device_plane: str = trace_reduce.TPU_PLANE,
         op_line: str = trace_reduce.TPU_OP_LINE,
         window_span: str = trace_reduce.WINDOW_SPAN):
    """``exchange_ms`` of one trace file, for the device plane
    ``trace_stages`` reads; None with fewer than two wave edges."""
    from jax.profiler import ProfileData

    edges = trace_stages.read(path, device_plane, op_line,
                              window_span)["edges"]
    if len(edges) < 2:
        return None
    a, b = edges[0], edges[-1]
    paths = trace_stages.op_paths(path, device_plane)
    for plane in sorted(ProfileData.from_file(path).planes,
                        key=lambda p: p.name):
        if not plane.name.startswith(device_plane):
            continue
        ops = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
               for ev in trace_reduce._events(plane, op_line)]
        if not ops:
            continue
        known = {}
        spans = []
        for s, e, name in ops:
            hit = known.get(name)
            if hit is None:
                hit = known[name] = in_scope(paths.get(name, ""))
            if hit:
                spans.append((s, e))
        merged = trace_reduce.union(trace_reduce.clip(spans, a, b))
        return sum(e - s for s, e in merged) / 1e6 / (len(edges) - 1)
    return None


def for_run(ctx: dict):
    """``exchange_ms`` of a ``--trace 1`` run's stretch, else None."""
    if not ctx.get("trace"):
        return None
    try:
        return read(trace_reduce.newest_xplane(
            os.path.join(ctx["out_dir"], "trace")))
    except (OSError, ValueError, IndexError):  # IndexError: a cut file
        return None


def window_counts(ctx: dict):
    """``(exchange_rows, exchange_slots, waves)`` summed over the
    window's dispatches; None where the program does not count them."""
    entries = ctx["window"]["entries"]
    if not entries or any(e.get("exchange_rows") is None
                          for e in entries):
        return None
    return (sum(e["exchange_rows"] for e in entries),
            sum(e["exchange_slots"] for e in entries),
            sum(e["waves"] for e in entries))
