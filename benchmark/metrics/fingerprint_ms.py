"""fingerprint_ms: device time per wave in the wave program's
``fingerprint`` scope, the successors' fingerprints: the union of the
scope's op intervals over the traced stretch's counted span, per wave
(see ``benchmark/trace_stages.py``). Moves ``states_per_s``."""

from benchmark import trace_stages


def read(ctx):
    return trace_stages.per_wave(ctx, "stage_ms", "fingerprint")
