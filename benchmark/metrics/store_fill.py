"""store_fill: admitted rows over the rows the store's append trips
carried, over the window's dispatches (each ``dispatch_log`` entry's
``novel`` and ``append_slots``). The store appends a wave's admitted
rows in chunks, and a trip pays for every row of its chunk, so this is
the share of the append's work spent on rows that stay in the arena.
On a mesh ``novel`` counts every shard and ``append_slots`` the slowest
shard's trips, so the admitted rows are taken per shard. Reported beside
the traced stretch's stage times, as ``probe_fill`` is, so None in a run
without a stretch, and where the program does not count the slots.
Moves ``states_per_s``."""


def read(ctx):
    entries = ctx["window"]["entries"]
    if (not ctx.get("trace")
            or any(e.get("append_slots") is None for e in entries)):
        return None
    slots = sum(e["append_slots"] for e in entries)
    if not slots:
        return None
    shards = ctx["config"].get("shards", 1)
    return sum(e["novel"] for e in entries) / shards / slots
