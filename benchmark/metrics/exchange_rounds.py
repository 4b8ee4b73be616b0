"""exchange_rounds: rounds of the shard exchange per wave, over the
window's dispatches (each ``dispatch_log`` entry's ``exchange_rounds``
over its ``waves``). A round sends each owner one bucket of a sender's
balanced share of its successors; a wave whose fullest bucket on any
shard holds more takes more rounds, each paying the owner's local dedup,
probe and store again. Reported beside the traced stretch's stage
times, as ``probe_rounds`` is, so None in a run without a stretch, and
where the program does not count the rounds. Moves ``states_per_s``."""


def read(ctx):
    entries = ctx["window"]["entries"]
    waves = sum(e["waves"] for e in entries)
    if (not ctx.get("trace") or not waves
            or any(e.get("exchange_rounds") is None for e in entries)):
        return None
    return sum(e["exchange_rounds"] for e in entries) / waves
