"""probe_rounds: rounds of the visited-table probe loop per wave, over
the window's dispatches (each ``dispatch_log`` entry's
``probe_rounds``). Reported beside the traced stretch's stage times
(``probe_ms`` ÷ ``probe_rounds`` is a round's device time), so None in a
run without a stretch, and where the program does not count them.
Moves ``states_per_s``."""


def read(ctx):
    entries = ctx["window"]["entries"]
    waves = sum(e["waves"] for e in entries)
    if (not ctx.get("trace") or not waves
            or any(e.get("probe_rounds") is None for e in entries)):
        return None
    return sum(e["probe_rounds"] for e in entries) / waves
