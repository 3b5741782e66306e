"""The block-diffusion attention's share of its roofline: the least time the
chip's peaks allow for the work of the scope ``bd_attention`` in the traced
window, over the device seconds booked to that scope (``trace["scope_s"]``).

The work is counted from the configuration's shapes by the family's file
(``families/sdar_moe.py::bd_attention_work``): the four projections and the
two products over the LIVE (query, key) pairs alone, and the bytes of the
layer's input and output, the weights and q, k, v and o. A forward's least
time is the larger of its FLOPs over ``bf16_flops_per_s`` and its bytes over
``hbm_bytes_per_s`` (``peaks.json``). A trained sequence costs four forwards
(the forward, the rematerialised forward and a backward of two), an
evaluated one a forward. Trained sequences: the counter
``bd_noisy_positions`` of the traced time steps' ``guard`` spans over the
sequence length; evaluated ones: the ``acc_matrix`` dispatches of those time
steps times M x C x N. So a kernel that skips the masked tiles reads a higher
share for the same work, and none can read above 100. Nothing to read where
the trace has no such scope or the program counts no noisy position."""

from benchmark import family_of
from benchmark import run as bench


def _traced_spans(records):
    """``cat="round"`` spans of the time steps after the window's (the
    traced ones), or None where the window has no time step."""
    steps = records.get("time_steps") or []
    if not steps:
        return None
    from feddrift_tpu.obs import spans
    last = max(s["t"] for s in steps)

    def after(span):
        t = span.get("args", {}).get("iteration")
        return t is not None and t > last
    return [s for s in spans.get_recorder().spans()
            if s["cat"] == "round" and after(s)]


def read(records, trace, cell):
    scope_s = (trace or {}).get("scope_s") or {}
    seconds = scope_s.get("bd_attention")
    traced = _traced_spans(records)
    if not seconds or traced is None:
        return None
    _, config, traffic, sizes = bench.load_cell(bench.load_manifest(),
                                                cell["name"])
    arch = config["arch"]
    work = getattr(family_of(arch), "bd_attention_work", None)
    if work is None:
        return None
    prog = {**config["program"], **traffic["program"], **sizes["program"]}
    noisy = sum(s["args"]["bd_noisy_positions"] for s in traced
                if "bd_noisy_positions" in s.get("args", {}))
    evals = sum(1 for s in traced if s["name"] == "dispatch"
                and s.get("args", {}).get("fn") == "acc_matrix")
    if not noisy:
        return None
    forwards = 4 * noisy / arch["seq_len"] \
        + evals * prog["concept_num"] * records["clients"] \
        * prog["sample_num"]
    flops, nbytes = work(arch)
    peak = records["peaks"][records["device_kind"]]
    least = forwards * max(flops / peak["bf16_flops_per_s"],
                           nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * least / seconds
