"""What ``pairs_trained_per_round`` and ``held_expert_assignments_per_token``
share: the counts that the program's scanned round returns
(``core/step.py::_round_body_scan``), which the runner fetches with the
round's losses, adds to its counters ``pairs_trained``, ``expert_tokens`` and
``expert_assignments_held``, and sets on that fetch's ``guard`` span
(``cat="round"``) under the same names. A reader is handed no snapshot of a
counter at the window's start, so it sums the counters' increments of the
window's rounds as the spans carry them. A program whose rounds return no
counts, as every program under ``client_axis="vmap"``, leaves nothing to
read."""

from benchmark.metrics._round_spans import window


def rounds_with(records, key):
    """(the window's rounds, the ``args`` of its spans that carry ``key``),
    or None where there is none."""
    found = window(records)
    if found is None:
        return None
    steps, spans = found
    args = [s["args"] for s in spans if key in s.get("args", {})]
    return (sum(s["rounds"] for s in steps), args) if args else None
