"""IFCA's rule: at the start of a time step and after every round each
client goes to the model that is most accurate on its current data.

Ties and near-ties flip on rounding, so the program's choice is not held to
the reference's argmax itself: the number compared, ``assign_regret``, is
the most accuracy that any client's model, as the program chose it, gives
away against the best model by the reference's own count."""

import numpy as np

NUMBER = "assign_regret"
EVERY_ROUND = True       # the job re-assigns after each round of a time step


def choose(ref, t: int) -> np.ndarray:
    """[C] the model each client goes to now, by the reference's models."""
    return ref.eval_matrix(t)[0].argmax(axis=0)


def reading(ref, t: int, prog_assign: np.ndarray) -> float:
    """Read once the reference has followed time step ``t``'s rounds."""
    acc = ref.eval_matrix(t)[0] / ref.labels
    clients = np.arange(acc.shape[1])
    return float((acc.max(axis=0) - acc[prog_assign, clients]).max())
