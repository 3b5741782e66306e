"""(model, client) pairs that the dispatched ``train_round`` program runs, a
round: its ``dispatch`` span carries ``pairs_run`` (counter ``pairs_run``),
K x C_pad where the vmap body runs the K models a client that the algorithm
counted in its time weights (K = 1 under IFCA's hard assignment), M x C_pad
where it runs every pair. The scanned round sets no such key: nothing to
read there (``pairs_trained_per_round`` is its count)."""

from benchmark.metrics._round_counts import rounds_with


def read(records, trace, cell):
    found = rounds_with(records, "pairs_run")
    if found is None:
        return None
    rounds, args = found
    return sum(a["pairs_run"] for a in args) / rounds
