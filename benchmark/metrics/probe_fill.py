"""probe_fill: candidates over the rows the visited-table probe's rounds
carried, over the window's dispatches (each ``dispatch_log`` entry's
``candidates`` and ``probe_slots``). A round pays for every row it
carries, so this is the share of the probe's work spent on rows that
have something to probe. On a mesh ``candidates`` counts every shard
and ``probe_slots`` the slowest shard's rounds, so the candidates are
taken per shard. Reported beside the traced stretch's stage times, as
``probe_rounds`` is, so None in a run without a stretch, and where the
program does not count the slots. Moves ``states_per_s``."""


def read(ctx):
    entries = ctx["window"]["entries"]
    if (not ctx.get("trace")
            or any(e.get("probe_slots") is None for e in entries)):
        return None
    slots = sum(e["probe_slots"] for e in entries)
    if not slots:
        return None
    shards = ctx["config"].get("shards", 1)
    return sum(e["candidates"] for e in entries) / shards / slots
