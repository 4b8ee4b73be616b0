"""compile_s: seconds the first check spent in ahead-of-time compiles
(or loading them from the persistent cache) before the window opened,
the program's own ``checker.compile_sec``. Moves ``setup_s``."""


def read(ctx):
    return ctx.get("compile_s")
