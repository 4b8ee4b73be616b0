"""host_ms_per_dispatch: the program's host loop's own time per
dispatch, in ms: each window dispatch's ``host_s`` (its launch and its
processing, less the blocking stats read), averaged. Reported beside
the traced stretch's idle time by program span, so None in a run
without a stretch, and where the program does not record it. Moves
``states_per_s``."""


def read(ctx):
    entries = ctx["window"]["entries"]
    if (not ctx.get("trace") or not entries
            or any(e.get("host_s") is None for e in entries)):
        return None
    return 1000 * sum(e["host_s"] for e in entries) / len(entries)
