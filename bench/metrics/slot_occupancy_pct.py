"""Occupied slots (vehicles the program scheduled) over the slots the
compiled window executes per round, averaged over the traced rounds."""


def read(ctx):
    recs, executed = ctx["records"], ctx["executed_slots"]
    if not recs or not executed:
        return None
    occ = sum(r["n_scheduled"] for r in recs) / len(recs)
    return 100.0 * occ / executed
