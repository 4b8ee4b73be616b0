"""sym_fold_share: the successors that the symmetry representative
folded onto another member of their orbit, over all successors, over
the window's dispatches (each ``dispatch_log`` entry's ``sym_folded``
and ``successors``). Where it is high the fingerprint stage's
representative does real work and the table holds half as many rows.
Read in traced runs, beside the stage times (``fingerprint_ms`` times
the representative); None in a run without a stretch, and where the
program does not count the folds (no symmetry, or a program that
predates the counter). Moves ``states_per_s``."""


def read(ctx):
    entries = ctx["window"]["entries"]
    if (not ctx.get("trace")
            or any(e.get("sym_folded") is None for e in entries)):
        return None
    successors = sum(e["successors"] for e in entries)
    if not successors:
        return None
    return sum(e["sym_folded"] for e in entries) / successors
