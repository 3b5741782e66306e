"""Masked positions of the noisy copy, in percent of its positions, over the
window's training steps: counters ``bd_masked_positions`` /
``bd_noisy_positions``, which the block-diffusion model's forward returns and
the scanned round sums over its trained pairs' local steps (set on the
``guard`` span's ``args`` as ``expert_tokens`` is). About 50 under a noise
level uniform on [1e-3, 1]: it says that the traffic is as stated. Nothing to
read from a model that does not count them."""

from benchmark.metrics._round_counts import rounds_with


def read(records, trace, cell):
    found = rounds_with(records, "bd_noisy_positions")
    if found is None:
        return None
    noisy = sum(a["bd_noisy_positions"] for a in found[1])
    masked = sum(a["bd_masked_positions"] for a in found[1])
    return 100.0 * masked / noisy if noisy else None
