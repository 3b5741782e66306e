"""Optimizer ``sgd``: plain gradient descent, as the program's
``client_optimizer: "sgd"`` is (``optax.sgd(lr)``): no decay, no momentum,
no state. It has no moment, so a cell that trains with it has no
``moment_gap*``, ``first_grad_gap*`` or ``moment_store_gap`` to compare.
The interface is set out in ``amsgrad.py``.
"""

from __future__ import annotations

HYPER = ("lr",)
MOMENTS = ()
RECENT = None
FIRST_GRAD = None


def new_state(p: dict) -> dict:
    return {}


def update(p: dict, g: dict, o: dict, hyper: dict) -> tuple[dict, dict]:
    lr = hyper["lr"]
    return {k: (p[k] - lr * g[k]).astype(p[k].dtype) for k in p}, o


def moments_of(program_state) -> dict:
    return {}
