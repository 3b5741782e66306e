"""Optimizer ``amsgrad``: AMSGrad with decayed weights, as the program's
``client_optimizer: "adam"`` is (``optax.chain(add_decayed_weights(wd),
amsgrad(lr))``).

An optimizer file holds the comparison's side of one client optimizer: the
hyper-parameters it reads, a fresh state, one plain update, the moments its
state has, which of them the comparison reads, and where they sit in the
optimizer state that the program's round program returns.
"""

from __future__ import annotations

import jax.numpy as jnp

HYPER = ("lr", "wd", "b1", "b2", "eps")   # read from the configuration's
                                          # "optimizer" group and lr, wd
MOMENTS = ("mu", "nu_max")     # the moment trees compared, each shaped as
                               # the parameters
RECENT = "mu"                  # the recent gradients as the optimizer got
                               # them: moment_gap*, moment_store_gap
FIRST_GRAD = "nu_max"          # the running maximum of the bias-corrected
                               # second moment keeps the time step's largest
                               # squared gradients, which are its first:
                               # first_grad_gap*


def new_state(p: dict) -> dict:
    z = {k: jnp.zeros_like(v) for k, v in p.items()}
    return {"count": jnp.zeros((), jnp.int32), "mu": z, "nu": dict(z),
            "nu_max": dict(z)}


def update(p: dict, g: dict, o: dict, hyper: dict) -> tuple[dict, dict]:
    """One step: (parameters, state) after the gradient ``g``."""
    lr, wd, b1, b2, eps = (hyper[k] for k in HYPER)
    count = o["count"] + 1
    new_p, mu, nu, nu_max = {}, {}, {}, {}
    for k in p:
        gk = (g[k] + wd * p[k]).astype(p[k].dtype)
        mu[k] = b1 * o["mu"][k] + (1 - b1) * gk
        nu[k] = b2 * o["nu"][k] + (1 - b2) * gk * gk
        mu_hat = mu[k] / (1 - b1 ** count)
        nu_hat = nu[k] / (1 - b2 ** count)
        nu_max[k] = jnp.maximum(o["nu_max"][k], nu_hat)
        upd = mu_hat / (jnp.sqrt(nu_max[k]) + eps)
        new_p[k] = (p[k] - lr * upd).astype(p[k].dtype)
        mu[k] = mu[k].astype(p[k].dtype)
        nu[k] = nu[k].astype(p[k].dtype)
        nu_max[k] = nu_max[k].astype(p[k].dtype)
    return new_p, {"count": count, "mu": mu, "nu": nu, "nu_max": nu_max}


def moments_of(program_state) -> dict:
    """``{moment: tree}`` out of the program's optimizer state: the chain's
    second link is optax's amsgrad, whose first element holds the moments."""
    state = program_state[1][0]
    return {"mu": state.mu, "nu_max": state.nu_max}
