"""eventually_pending_share: the admitted states that still carry an
uncleared eventually bit (no state on the path that first reached them
satisfied the eventually property), over all admitted states, over the
window's dispatches (each ``dispatch_log`` entry's
``eventually_pending`` and ``novel``). These are the rows whose bits
the properties stage must keep clearing and whose terminal states it
must flag. Read in traced runs, beside the stage times; None in a run
without a stretch, and where the program does not count them (no
eventually property, or a program that predates the counter). Moves
``states_per_s``."""


def read(ctx):
    entries = ctx["window"]["entries"]
    if (not ctx.get("trace")
            or any(e.get("eventually_pending") is None for e in entries)):
        return None
    admitted = sum(e["novel"] for e in entries)
    if not admitted:
        return None
    return sum(e["eventually_pending"] for e in entries) / admitted
