"""batch_occupancy: frontier rows expanded over the rows the waves
dispatched (bucket x waves), over the window's dispatches. A wave pays
for its whole bucket however few rows it holds. Moves
``states_per_s``."""

from benchmark.costs import window_sums


def read(ctx):
    s = window_sums(ctx["window"]["entries"])
    return s["rows"] / s["slots"] if s["slots"] else None
