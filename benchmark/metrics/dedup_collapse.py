"""dedup_collapse: distinct successors that reached the visited table
over successors generated, over the window's dispatches: the share the
wave's local dedup leaves for the table probe. Moves
``states_per_s``."""

from benchmark.costs import window_sums


def read(ctx):
    s = window_sums(ctx["window"]["entries"])
    return s["candidates"] / s["successors"] if s["successors"] else None
