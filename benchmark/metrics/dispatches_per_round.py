"""Tracked program dispatches per round: the ``dispatch`` spans of the
window's time steps (one per call through ``TrainStep``'s tracked wrappers:
``train_round``, ``acc_matrix``, ``train_iteration_eval``, ...) over its
rounds. The time-step boundary's dispatches are spread over the rounds.
Eager dispatches (slices, ``jnp.asarray`` of masks, the optimizer-state
init's ops) are not counted."""

from benchmark.metrics._round_spans import per_round


def read(records, trace, cell):
    return per_round(records, "dispatch")
