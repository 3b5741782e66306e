"""Tracked program dispatches per round: the ``dispatch`` spans of the
window's time steps (one per call through ``TrainStep``'s tracked wrappers:
``train_round``, ``acc_matrix``, ``train_iteration_eval``,
``fresh_opt_states``, ...) over its rounds. The time-step boundary's
dispatches are spread over the rounds: since PR 26 the time step's fresh
optimizer states are one of them (one tracked program where some four
hundred eager ops went uncounted), so the cell reads 3.2 where it read 3.0,
and the rehearsal's tiny job 5.0 (ten in 2 rounds) where it read 4.5. Eager
dispatches (slices, ``jnp.asarray`` of masks) are not counted."""

from benchmark.metrics._round_spans import per_round


def read(records, trace, cell):
    return per_round(records, "dispatch")
