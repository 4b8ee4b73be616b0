"""exchange_ms: device time per wave in the mesh wave's ``exchange``
scope, the sender-side duplicate collapse, the owner bucketing and the
all-to-alls: the union of the first chip's ops in the scope over the
traced stretch's counted span, per wave (see ``benchmark/exchange.py``).
Moves ``states_per_s``."""

from benchmark import exchange


def read(ctx):
    return exchange.for_run(ctx)
