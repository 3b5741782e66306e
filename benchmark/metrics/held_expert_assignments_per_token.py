"""(token, chosen expert) assignments that fell on experts this chip holds,
a token: counters ``expert_assignments_held`` / ``expert_tokens``, both summed
over the expert layers of the window's training steps, so the quotient is the
mean over those layers. ``num_experts_per_tok`` x held / routed experts when
routing is even (0.375 at 6 x 8 / 128), which is what ``forward_macs`` and so
``train_step_mfu`` count in expectation; the others' part of the routed sum
is computed on other chips and left out here."""

from benchmark.metrics._round_counts import rounds_with


def read(records, trace, cell):
    found = rounds_with(records, "expert_tokens")
    if found is None:
        return None
    tokens = sum(a["expert_tokens"] for a in found[1])
    held = sum(a["expert_assignments_held"] for a in found[1])
    return held / tokens if tokens else None
