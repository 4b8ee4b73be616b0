"""exchange_fill: successor rows sent to another shard over the rows the
all-to-alls carried between shards, over the window's dispatches (each
``dispatch_log`` entry's ``exchange_rows`` and ``exchange_slots``). A
shard sends at most its wave's ``B*F`` successors into ``(n-1)*B*F``
off-shard slots, so the design caps it at ``1/(n-1)``; the rest is
padding. None where the program does not count them. Moves
``states_per_s``."""

from benchmark import exchange


def read(ctx):
    counts = exchange.window_counts(ctx)
    if counts is None or not counts[1]:
        return None
    return counts[0] / counts[1]
