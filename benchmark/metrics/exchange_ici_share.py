"""exchange_ici_share: the least bytes a shard has to send to the others
in a wave (the window's ``exchange_rows`` per shard per wave, each row
its packed state and its fingerprints and property bits), over
``exchange_ms``, as a share of one chip's interconnect peak
(``peaks.json``). A lower bound: only rows that carry a successor
count, over the whole scope's time. Moves ``states_per_s``."""

from benchmark import costs, exchange


def read(ctx):
    counts = exchange.window_counts(ctx)
    ms = exchange.for_run(ctx)
    if counts is None or not counts[2] or not ms:
        return None
    shards = ctx["config"].get("shards", len(ctx["devices"]))
    wave_bytes = exchange.shard_wave_bytes(counts[0], shards, counts[2],
                                           ctx["config"]["row_bits"])
    peak = costs.peak(ctx["peaks"], ctx["devices"][0].device_kind,
                      "ici_bits_per_s")
    return exchange.ici_share(wave_bytes, ms, peak)
