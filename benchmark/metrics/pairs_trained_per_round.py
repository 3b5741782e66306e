"""(model, client) pairs whose local loop ran, a round: the scanned round
skips a pair whose time weights sum to 0 and returns the count of the taken
branches of its ``cond`` (counter ``pairs_trained``). Under IFCA's hard
assignment each participating client trains one model: C of the M x C pairs;
M x C where inactive pairs are not skipped."""

from benchmark.metrics._round_counts import rounds_with


def read(records, trace, cell):
    found = rounds_with(records, "pairs_trained")
    if found is None:
        return None
    rounds, args = found
    return sum(a["pairs_trained"] for a in args) / rounds
