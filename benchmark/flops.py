"""Operation counts from a configuration's shapes.

The counts are analytic: 2 x multiply-accumulates of every convolution and
dense layer of the forward pass, counted by the family's file
(``families/<family>.py``) from the ``arch`` block of the configuration's
file. Normalisation, activations, the loss and the optimizer
count nothing, and neither does anything the program recomputes or masks
out. XLA's ``cost_analysis`` is not used: it counts a ``lax.scan`` body once
and means different things at its two capture levels (PERF.md section 6).
"""

from __future__ import annotations

import math

from benchmark import family_of


def forward_macs(arch: dict) -> int:
    """Multiply-accumulates of one sample's forward pass, as the family's
    file counts them."""
    return family_of(arch).forward_macs(arch)


def forward_flops(arch: dict) -> int:
    return 2 * forward_macs(arch)


def train_flops_per_example(arch: dict) -> int:
    """Forward plus backward of one example: three times the forward."""
    return 3 * forward_flops(arch)


def parameter_count(arch: dict) -> int:
    return sum(math.prod(shape)
               for _, shape, _ in family_of(arch).param_spec(arch))
