"""The yardstick's arithmetic: the chip's published peaks, and the
least bytes a wave of breadth-first search has to move.

A wave expands ``rows`` frontier rows and offers ``candidates``
distinct successors to the visited table, of which ``novel`` are new.
Whatever the implementation, it has to read each frontier row once,
read one 8-byte table slot per candidate, and write each new state's
row, its 8-byte fingerprint, its 8-byte parent fingerprint and its
4-byte property bits. A row is the model's state in the fewest whole
32-bit words that hold its declared lane widths (``row_bits`` in the
configuration file). Counting only these bytes makes the share of the
memory roofline a lower bound that cannot pass 100% when the time is
real device time. No metric reads ``wave_bytes`` yet: the trace has no
mark of a wave's edges until the program names its stages (PERF.md,
Open questions).
"""

from __future__ import annotations


class UnknownDevice(KeyError):
    """A device kind that ``peaks.json`` does not list."""


def peak(peaks: dict, kind: str, key: str) -> float:
    """One published peak of ``kind``; an unlisted kind is an error,
    never a default."""
    if kind not in peaks or kind == "source":
        raise UnknownDevice(f"{kind!r} is not in peaks.json "
                            f"({sorted(k for k in peaks if k != 'source')})")
    return float(peaks[kind][key])


def row_bytes(row_bits: int) -> int:
    return 4 * -(-int(row_bits) // 32)


def wave_bytes(rows: int, candidates: int, novel: int,
               row_bits: int) -> int:
    """The least bytes the waves that expanded ``rows`` rows moved."""
    rb = row_bytes(row_bits)
    return rows * rb + candidates * 8 + novel * (rb + 8 + 8 + 4)


def window_sums(entries: list) -> dict:
    """Totals over a window's dispatch records (the program's
    ``dispatch_log`` entries)."""
    out = dict.fromkeys(("rows", "slots", "waves", "candidates",
                         "successors", "novel"), 0)
    for e in entries:
        out["rows"] += e["rows"]
        out["slots"] += e["bucket"] * e["waves"]
        out["waves"] += e["waves"]
        out["candidates"] += e["candidates"]
        out["successors"] += e["successors"]
        out["novel"] += e["novel"]
    return out
