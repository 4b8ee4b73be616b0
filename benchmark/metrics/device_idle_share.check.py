"""device_idle_share.check: 1 - device busy / traced window, from the
profiler trace taken inside a check-stream window. Moves
``states_per_s``."""


def read(ctx):
    trace = ctx.get("trace")
    return trace["idle_share"] if trace else None
