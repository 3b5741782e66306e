"""The whole step's share of the chips' peak: useful FLOPs over window wall
x chips x peak. Useful FLOPs are those of the active (model, client) pairs
only: pairs x local steps x batch x 3 x forward FLOPs, the forward counted
from the configuration's shapes (``benchmark/flops.py``). Masked pairs,
evaluations and recomputation count nothing. Where a round samples its
participants, the pairs of a time step count by the share of the clients
that take part (exact where every client has as many active models)."""


def read(records, trace, cell):
    steps = records["time_steps"]
    if not steps:
        return None
    peak = records["peaks"][records["device_kind"]]["bf16_flops_per_s"]
    examples = sum(s["active_pairs"] * s["rounds"] for s in steps) \
        * records["participants"] / records["clients"] \
        * records["local_steps"] * records["batch"]
    useful = examples * records["train_flops_per_example"]
    return 100.0 * useful / (records["window_s"] * records["chips"] * peak)
