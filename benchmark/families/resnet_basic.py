"""Family ``resnet_basic``: residual networks of basic blocks (two 3x3
convolutions and a shortcut) over images, per-batch normalisation, a mean
over the feature map and a dense head; one label per image.

A family file holds what is the model's and nothing that is the job's: the
parameters and how they are drawn, the plain forward pass, the loss and the
hits of a batch, the forward pass's operation count, the program's nesting
of the parameters, and the shapes of one sample. ``arch`` is the block of
that name in the configuration's file:

    {"family": "resnet_basic", "layout": "resnet18" | "resnet_cifar",
     "input": [H, W, C], "num_classes": K, "stem_filters": F,
     "stages": [{"filters": F, "blocks": B, "stride": S}, ...]}
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


# ----------------------------------------------------------------------
# parameters
def _blocks(arch: dict):
    """(name, cin, cout, stride, has_projection) per basic block."""
    cin = arch["stem_filters"]
    for s, stage in enumerate(arch["stages"]):
        cout = stage["filters"]
        for b in range(stage["blocks"]):
            stride = stage["stride"] if b == 0 else 1
            yield f"s{s}b{b}", cin, cout, stride, (stride != 1 or cin != cout)
            cin = cout


def param_spec(arch: dict) -> list[tuple[str, tuple[int, ...], str]]:
    """(name, shape, role) of every parameter in forward order; role is
    conv | dense | scale | bias."""
    spec = []

    def norm(prefix, c):
        spec.append((f"{prefix}/scale", (c,), "scale"))
        spec.append((f"{prefix}/bias", (c,), "bias"))

    c0 = arch["stem_filters"]
    spec.append(("stem/conv", (3, 3, arch["input"][2], c0), "conv"))
    norm("stem/norm", c0)
    cout = c0
    for name, cin, cout, _stride, proj in _blocks(arch):
        spec.append((f"{name}/conv1", (3, 3, cin, cout), "conv"))
        norm(f"{name}/norm1", cout)
        spec.append((f"{name}/conv2", (3, 3, cout, cout), "conv"))
        norm(f"{name}/norm2", cout)
        if proj:
            spec.append((f"{name}/proj", (1, 1, cin, cout), "conv"))
            norm(f"{name}/projnorm", cout)
    spec.append(("head/kernel", (cout, arch["num_classes"]), "dense"))
    spec.append(("head/bias", (arch["num_classes"],), "bias"))
    return spec


def draw(role: str, key, shape: tuple[int, ...], num_models: int):
    """[num_models, *shape] float32 of one parameter: He-normal
    convolutions, a LeCun-normal head, unit scales and zero biases. ``key``
    is the parameter's own (``weights.py`` folds its index in)."""
    if role == "scale":
        return jnp.ones((num_models, *shape), jnp.float32)
    if role == "bias":
        return jnp.zeros((num_models, *shape), jnp.float32)
    fan_in = math.prod(shape[:-1])
    std = math.sqrt((2.0 if role == "conv" else 1.0) / fan_in)
    return std * jax.random.normal(key, (num_models, *shape), jnp.float32)


def parameter_count(arch: dict) -> int:
    """The closed form, which the tests hold ``param_spec`` and the
    published counts to."""
    cin = arch["input"][2]
    n = 9 * cin * arch["stem_filters"] + 2 * arch["stem_filters"]
    cin = arch["stem_filters"]
    for stage in arch["stages"]:
        cout = stage["filters"]
        for block in range(stage["blocks"]):
            stride = stage["stride"] if block == 0 else 1
            n += 9 * cin * cout + 9 * cout * cout + 4 * cout
            if stride != 1 or cin != cout:
                n += cin * cout + 2 * cout
            cin = cout
    return n + cin * arch["num_classes"] + arch["num_classes"]


# ----------------------------------------------------------------------
# the forward pass, the loss and the hits
def _conv(x, k, stride, dtype=None):
    if dtype is not None:
        x, k = x.astype(dtype), k.astype(dtype)
    return jax.lax.conv_general_dilated(
        x, k, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)


def _norm(x, scale, bias):
    """Per-batch normalisation over (N, H, W), no running statistics."""
    x = x.astype(jnp.float32)
    mean = x.mean(axis=(0, 1, 2), keepdims=True)
    var = ((x - mean) ** 2).mean(axis=(0, 1, 2), keepdims=True)
    return (x - mean) / jnp.sqrt(var + 1e-5) * scale + bias


def forward(arch: dict, p: dict, x, dtype=None):
    """Logits [N, classes] of images [N, H, W, 3]. ``dtype`` lowers the
    convolutions' operands (the control), nothing else."""
    x = x.reshape((x.shape[0], *arch["input"]))
    h = jax.nn.relu(_norm(_conv(x, p["stem/conv"], 1, dtype),
                          p["stem/norm/scale"], p["stem/norm/bias"]))
    for name, _cin, _cout, stride, proj in _blocks(arch):
        y = _conv(h, p[f"{name}/conv1"], stride, dtype)
        y = jax.nn.relu(_norm(y, p[f"{name}/norm1/scale"],
                              p[f"{name}/norm1/bias"]))
        y = _conv(y, p[f"{name}/conv2"], 1, dtype)
        y = _norm(y, p[f"{name}/norm2/scale"], p[f"{name}/norm2/bias"])
        if proj:
            h = _norm(_conv(h, p[f"{name}/proj"], stride, dtype),
                      p[f"{name}/projnorm/scale"], p[f"{name}/projnorm/bias"])
        h = jax.nn.relu(y + h)
    feats = h.mean(axis=(1, 2))
    return jnp.matmul(feats, p["head/kernel"], precision=HIGHEST) \
        + p["head/bias"]


def nll(logits, y):
    """One loss per label: [N] of logits [N, classes] and labels [N]. The
    job takes the mean (training) or the sum (evaluation) over them."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0]


def hits(logits, y):
    """One 0/1 per label: whether the model's first choice is the label."""
    return logits.argmax(-1) == y


# ----------------------------------------------------------------------
# operations
def _out_size(size: int, stride: int) -> int:
    """Spatial size after a SAME-padded convolution."""
    return -(-size // stride)


def forward_macs(arch: dict) -> int:
    """Multiply-accumulates of one image's forward pass: every convolution
    and the dense head."""
    h, w, cin = arch["input"]
    macs = 0

    def conv(h, w, cin, cout, k, stride):
        ho, wo = _out_size(h, stride), _out_size(w, stride)
        return ho * wo * k * k * cin * cout, ho, wo

    m, h, w = conv(h, w, cin, arch["stem_filters"], 3, 1)
    macs += m
    cin = arch["stem_filters"]
    for stage in arch["stages"]:
        cout = stage["filters"]
        for block in range(stage["blocks"]):
            stride = stage["stride"] if block == 0 else 1
            m1, ho, wo = conv(h, w, cin, cout, 3, stride)
            m2, _, _ = conv(ho, wo, cout, cout, 3, 1)
            macs += m1 + m2
            if stride != 1 or cin != cout:
                md, _, _ = conv(h, w, cin, cout, 1, stride)
                macs += md
            h, w, cin = ho, wo, cout
    macs += cin * arch["num_classes"]
    return macs


# ----------------------------------------------------------------------
# the program's side: its nesting of the parameters, one sample's shapes
def to_program_tree(arch: dict, flat: dict) -> dict:
    """The flat parameter dict in the nesting the program's flax modules
    use, by the layout the configuration names."""
    layout = arch["layout"]

    def norm(prefix):
        return {"scale": flat[f"{prefix}/scale"], "bias": flat[f"{prefix}/bias"]}

    def block(name):
        b = {"Conv_0": {"kernel": flat[f"{name}/conv1"]},
             "_Norm_0": norm(f"{name}/norm1"),
             "Conv_1": {"kernel": flat[f"{name}/conv2"]},
             "_Norm_1": norm(f"{name}/norm2")}
        if f"{name}/proj" in flat:
            b["Conv_2"] = {"kernel": flat[f"{name}/proj"]}
            b["_Norm_2"] = norm(f"{name}/projnorm")
        return b

    stem = {"Conv_0": {"kernel": flat["stem/conv"]},
            "_Norm_0": norm("stem/norm")}
    head = {"Dense_0": {"kernel": flat["head/kernel"],
                        "bias": flat["head/bias"]}}
    names = [f"s{s}b{b}" for s, st in enumerate(arch["stages"])
             for b in range(st["blocks"])]
    if layout == "resnet18":
        tree = dict(stem)
        tree.update({f"BasicBlock_{i}": block(n) for i, n in enumerate(names)})
        tree.update(head)
        return tree
    if layout == "resnet_cifar":
        n0 = arch["stages"][0]["blocks"]
        trunk = dict(stem)
        trunk.update({f"BasicBlock_{i}": block(n)
                      for i, n in enumerate(names[:n0])})
        tail = {f"BasicBlock_{i}": block(n) for i, n in enumerate(names[n0:])}
        tail.update(head)
        return {"ResNetFeatures_0": trunk, "ResNetServerTail_0": tail}
    raise KeyError(f"unknown parameter layout {layout!r}")


def sample_shapes(arch: dict) -> dict:
    """Shapes and types of one sample, for lowering a round program with
    shapes only (``sizing.py``)."""
    return {"x": (tuple(arch["input"]), "float32"), "y": ((), "int32"),
            "num_classes": arch["num_classes"]}
