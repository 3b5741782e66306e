"""``benchmark/flops.py`` against hand counts and against XLA's own count of
a single un-scanned forward pass."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import flops, reference, weights  # noqa: E402


def _arch(name):
    with open(os.path.join(ROOT, "benchmark", "configs", f"{name}.json")) as f:
        return json.load(f)["arch"]


def test_resnet18_forward_by_hand():
    # stem 32x32x27x64; stage 1: four 3x3 convs at 32x32x64x64; each later
    # stage: one strided 3x3 cin->cout, three 3x3 cout->cout, one 1x1
    # projection, at half the resolution; head 512x10
    hw = 32 * 32
    want = hw * 27 * 64 + 4 * hw * 9 * 64 * 64
    for cin, cout, side in ((64, 128, 16), (128, 256, 8), (256, 512, 4)):
        px = side * side
        want += px * 9 * cin * cout + 3 * px * 9 * cout * cout \
            + px * cin * cout
    want += 512 * 10
    assert flops.forward_macs(_arch("cifar10_resnet18")) == want
    assert want == pytest.approx(0.556e9, rel=0.01)


def test_resnet20_forward_by_hand():
    hw = 32 * 32
    want = hw * 27 * 16 + 6 * hw * 9 * 16 * 16
    for cin, cout, side in ((16, 32, 16), (32, 64, 8)):
        px = side * side
        want += px * 9 * cin * cout + 5 * px * 9 * cout * cout \
            + px * cin * cout
    want += 64 * 10
    assert flops.forward_macs(_arch("cifar10_resnet20")) == want
    assert want == pytest.approx(40.8e6, rel=0.01)


@pytest.mark.parametrize("name,params", [("cifar10_resnet18", 11_173_962),
                                         ("cifar10_resnet20", 272_474)])
def test_parameter_counts_are_the_published_ones(name, params):
    arch = _arch(name)
    assert flops.parameter_count(arch) == params
    import math
    assert sum(math.prod(s) for _, s, _ in reference.param_spec(arch)) == params
    # the family's closed form, counted another way than its param_spec
    from benchmark.families import resnet_basic
    assert resnet_basic.parameter_count(arch) == params


def test_training_counts_three_forwards():
    arch = _arch("cifar10_resnet20")
    assert flops.train_flops_per_example(arch) == 6 * flops.forward_macs(arch)


def test_agrees_with_xla_on_an_unscanned_forward_at_a_tiny_width():
    import jax
    import numpy as np
    arch = {"family": "resnet_basic", "layout": "resnet_cifar",
            "input": [32, 32, 3], "num_classes": 10, "stem_filters": 16,
            "stages": [{"filters": 16, "blocks": 1, "stride": 1},
                       {"filters": 32, "blocks": 1, "stride": 2}]}
    p = {k: v[0] for k, v in weights.make_weights(arch, 3, 1).items()}
    x = np.zeros((4, 32, 32, 3), np.float32)
    cost = jax.jit(lambda p, x: reference.forward(arch, p, x)) \
        .lower(p, x).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    mine = 4 * flops.forward_flops(arch)
    # XLA leaves out the products with the SAME padding's zeros at the
    # borders (a 3x3 tap at 32x32 loses 6 %, at 16x16 12 %) and adds the
    # normalisations' and activations' elementwise work
    assert 0.9 * mine <= cost["flops"] <= 1.25 * mine


def test_unknown_family_is_an_error():
    with pytest.raises(KeyError):
        flops.forward_macs({"family": "transformer"})
