"""wave_device_ms: the device's busy time (the union over all ops) per
wave, over the traced stretch's counted span of whole waves (see
``benchmark/trace_stages.py``). Moves ``states_per_s``."""

from benchmark import trace_stages


def read(ctx):
    return trace_stages.per_wave(ctx, "wave_device_ms")
