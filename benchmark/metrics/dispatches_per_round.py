"""Tracked program dispatches per round: the ``dispatch`` spans of the
window's time steps (one per call through ``TrainStep``'s tracked wrappers:
``train_round``, ``acc_matrix``, ``train_iteration_eval``,
``fresh_opt_states``, ...) over its rounds. The time-step boundary's
dispatches are spread over the rounds: the time step's fresh optimizer
states are one of them since PR 26 (one tracked program where some four
hundred eager ops went uncounted), and since PR 32 an ``acc_matrix`` that the
host already holds for the pool and time step is served from the store and
not dispatched: 13 in the cells' 5 rounds (5 ``train_round``, 1
``fresh_opt_states``, 7 ``acc_matrix``), 2.6, and 7 in the 2 rounds of the
rehearsal's tiny job, 3.5. Eager dispatches (slices, ``jnp.asarray`` of
masks) are not counted."""

from benchmark.metrics._round_spans import per_round


def read(records, trace, cell):
    return per_round(records, "dispatch")
