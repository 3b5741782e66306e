"""Operation counts from a configuration's shapes.

The counts are analytic: 2 x multiply-accumulates of every convolution and
dense layer of the forward pass, from the ``arch`` block of the
configuration's file. Normalisation, activations, the loss and the optimizer
count nothing, and neither does anything the program recomputes or masks
out. XLA's ``cost_analysis`` is not used: it counts a ``lax.scan`` body once
and means different things at its two capture levels (PERF.md section 6).
"""

from __future__ import annotations


def _out_size(size: int, stride: int) -> int:
    """Spatial size after a SAME-padded convolution."""
    return -(-size // stride)


def forward_macs(arch: dict) -> int:
    """Multiply-accumulates of one image's forward pass."""
    if arch["family"] != "resnet_basic":
        raise KeyError(f"no operation count for family {arch['family']!r}")
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


def forward_flops(arch: dict) -> int:
    return 2 * forward_macs(arch)


def train_flops_per_example(arch: dict) -> int:
    """Forward plus backward of one example: three times the forward."""
    return 3 * forward_flops(arch)


def parameter_count(arch: dict) -> int:
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
