"""A family that lives in the tests: two dense layers with a relu between
them, over the last axis of the input. On ``[N, F]`` inputs with one label
a sample it is the program's ``fnn`` (``models/mlp.py::FeedForwardNN``); on
``[N, L, F]`` inputs with ``[N, L]`` labels its loss is per token. The tests
put it in ``sys.modules`` as ``benchmark.families.dense2_fixture``: no file
under ``benchmark/`` knows it.

    arch = {"family": "dense2_fixture", "input": [F], "hidden": H,
            "num_classes": K}
"""

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def param_spec(arch):
    f, h, k = arch["input"][-1], arch["hidden"], arch["num_classes"]
    return [("fc1/kernel", (f, h), "dense"), ("fc1/bias", (h,), "bias"),
            ("fc2/kernel", (h, k), "dense"), ("fc2/bias", (k,), "bias")]


def draw(role, key, shape, num_models):
    if role == "bias":
        return jnp.zeros((num_models, *shape), jnp.float32)
    return math.sqrt(1.0 / shape[0]) * jax.random.normal(
        key, (num_models, *shape), jnp.float32)


def forward(arch, p, x, dtype=None):
    def dense(x, k, b):
        if dtype is not None:
            x, k = x.astype(dtype), k.astype(dtype)
        return jnp.matmul(x, k, precision=HIGHEST).astype(jnp.float32) + b
    h = jax.nn.relu(dense(x, p["fc1/kernel"], p["fc1/bias"]))
    return dense(h, p["fc2/kernel"], p["fc2/bias"])


def nll(logits, y):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, y[..., None], axis=-1)[..., 0]


def hits(logits, y):
    return logits.argmax(-1) == y


def forward_macs(arch):
    f, h, k = arch["input"][-1], arch["hidden"], arch["num_classes"]
    return f * h + h * k


def to_program_tree(arch, flat):
    return {"Dense_0": {"kernel": flat["fc1/kernel"], "bias": flat["fc1/bias"]},
            "Dense_1": {"kernel": flat["fc2/kernel"], "bias": flat["fc2/bias"]}}


def sample_shapes(arch):
    return {"x": (tuple(arch["input"]), "float32"), "y": ((), "int32"),
            "num_classes": arch["num_classes"]}
