"""XLA cost/memory accounting: what each compiled program costs the chip.

Every bench artifact before this module reported ``"mfu_estimate": null``:
the analytic FLOP rules could guess at compute, but nothing observed what
XLA actually compiled. This module closes that gap with three pieces:

**Program cost capture.** ``capture()`` runs at jit-compile time (hooked
from ``TrainStep._note_signature`` on every *first* argument signature):
it re-lowers the jitted program with the call's arguments and harvests
XLA's own accounting — ``cost_analysis()`` FLOPs / bytes accessed, and
(at the ``"compiled"`` level) ``memory_analysis()`` argument / output /
temp HBM sizes. Each capture emits one ``program_cost`` event and
refreshes the ``program_flops{fn=...}`` / ``program_bytes_accessed`` /
``program_peak_hbm_bytes`` gauges plus the cross-program
``hbm_peak_bytes`` high-water gauge. Levels (``cfg.cost_model``):

    off       no capture
    lowered   trace + lower only; FLOPs and bytes accessed (cheap —
              no second XLA compile; the default for runs)
    compiled  additionally compile the lowered module and read
              ``memory_analysis()`` — exact static HBM accounting, at
              the price of one extra XLA compile per program (bench.py
              uses this; the persistent compile cache halves the hit)

**Live HBM watermarks.** ``record_hbm_watermark()`` reads
``device.memory_stats()`` (``bytes_in_use`` / ``peak_bytes_in_use``),
emits an ``hbm_watermark`` event and folds the live peak into the
``hbm_peak_bytes`` gauge. CPU backends expose no memory stats: the call
returns ``None`` and emits nothing — graceful, never an error.

**Peaks + roofline.** ``peak_flops()`` / ``peak_bytes_per_s()`` give the
denominator MFU needs from ONE table, ``DEVICE_PEAKS``, keyed by the
``device_kind`` string the chip reports and carrying each entry's
source. A kind that is not in the table raises; a CPU has no peak, so
MFU and roofline utilization of a CPU run are ``None`` ("not measured"),
never a measured stand-in under a device metric's name. ``roofline()``
combines achieved FLOP/s and bytes/s against those peaks and names the
binding resource.
"""

from __future__ import annotations

import logging
import threading
from dataclasses import asdict, dataclass
from typing import Any

from feddrift_tpu.obs import events, instruments

log = logging.getLogger("feddrift_tpu")

CAPTURE_LEVELS = ("off", "lowered", "compiled")

# Published per-chip peaks, keyed by ``jax.devices()[0].device_kind``. Only
# what the source states: v5e has no published f32 matmul peak, so an f32
# run has no MFU denominator (None), not an invented one.
DEVICE_PEAKS = {
    "TPU v5 lite": {
        "flops": {"bfloat16": 197e12, "int8": 393e12},
        "bytes_per_s": 819e9,
        "source": "Google Cloud documentation, 'TPU v5e' "
                  "(197 TFLOP/s bf16, 393 TOP/s int8, 819 GB/s HBM)",
    },
}


def device_info() -> dict[str, Any]:
    """What this process computes on, as JAX reports it. Every artifact
    that carries a number also carries these three fields."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


@dataclass
class ProgramCost:
    """XLA's accounting of ONE compiled program (one jit entry point)."""

    fn: str
    level: str                          # "lowered" | "compiled"
    flops: float | None = None          # per execution of the program
    bytes_accessed: float | None = None
    # Pre-optimization accounting of the same program: buffers counted at
    # the widths the program DECLARES. Backend optimizers may promote
    # narrow dtypes (XLA:CPU emulates bf16 matmuls/convs in f32, adding
    # convert traffic), so the optimized-HLO `bytes_accessed` above can
    # overstate a bf16 program's portable cost; this field is the
    # backend-independent dtype-economics signal the precision axis gates.
    lowered_bytes_accessed: float | None = None
    argument_bytes: int | None = None   # memory_analysis (compiled only)
    output_bytes: int | None = None
    temp_bytes: int | None = None
    generated_code_bytes: int | None = None
    peak_hbm_bytes: int | None = None   # see _peak_from_memory_analysis

    def to_event_fields(self) -> dict[str, Any]:
        return {k: v for k, v in asdict(self).items() if v is not None}


# ----------------------------------------------------------------------
# Process-local store of captured program costs, keyed by jit entry-point
# name — the same names the jit_compile events carry.
_costs: dict[str, ProgramCost] = {}
_lock = threading.Lock()


def costs() -> dict[str, ProgramCost]:
    """Snapshot of every captured program cost (by entry-point name)."""
    with _lock:
        return dict(_costs)


def get(fn: str) -> ProgramCost | None:
    with _lock:
        return _costs.get(fn)


def clear() -> None:
    with _lock:
        _costs.clear()


def _cost_dict(obj) -> dict | None:
    """``cost_analysis()`` of a lowered or compiled program, or None where
    the backend has none."""
    try:
        cost = obj.cost_analysis()
    except Exception:
        return None
    return cost if isinstance(cost, dict) else None


def _peak_from_memory_analysis(mem) -> int | None:
    """Static peak-HBM estimate for one program.

    XLA reports a true ``peak_memory_in_bytes`` on some backends; where it
    is None (CPU) the sum argument + output + temp − aliased is the
    buffer-assignment upper bound: everything the executable touches that
    must be resident at once, donations already netted out via alias.
    """
    peak = getattr(mem, "peak_memory_in_bytes", None)
    if peak:
        return int(peak)
    total = 0
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes"):
        total += int(getattr(mem, attr, 0) or 0)
    total -= int(getattr(mem, "alias_size_in_bytes", 0) or 0)
    return total if total > 0 else None


def _set_gauges(pc: ProgramCost) -> None:
    reg = instruments.registry()
    if pc.flops is not None:
        reg.gauge("program_flops", fn=pc.fn).set(pc.flops)
    if pc.bytes_accessed is not None:
        reg.gauge("program_bytes_accessed", fn=pc.fn).set(pc.bytes_accessed)
    if pc.peak_hbm_bytes is not None:
        reg.gauge("program_peak_hbm_bytes", fn=pc.fn).set(pc.peak_hbm_bytes)
        peak = hbm_peak_bytes()
        if peak is not None:
            reg.gauge("hbm_peak_bytes").set(peak)


def refresh_gauges() -> None:
    """Re-populate the program-cost gauges from the store.

    bench.py resets the instrument registry after warm-up so its snapshot
    covers exactly the timed steady state — but the programs compiled (and
    were captured) *during* warm-up. This puts their gauges back without
    re-capturing anything.
    """
    for pc in costs().values():
        _set_gauges(pc)


def capture(fn: str, jit_fn, args: tuple, kwargs: dict | None = None,
            level: str = "lowered") -> ProgramCost | None:
    """Harvest XLA's cost/memory accounting for one jitted entry point.

    ``jit_fn`` is the jax.jit-wrapped callable and ``args``/``kwargs`` the
    exact call about to be dispatched (lowering with donated argnums is
    abstract — no buffer is consumed). Failures are never fatal: the cost
    model is evidence, not a gate, so any backend/API gap logs a warning
    and returns None.
    """
    if level == "off":
        return None
    if level not in CAPTURE_LEVELS:
        raise ValueError(f"unknown cost-capture level {level!r}; "
                         f"one of {CAPTURE_LEVELS}")
    try:
        lowered = jit_fn.lower(*args, **(kwargs or {}))
        pc = ProgramCost(fn=fn, level=level)
        cost = _cost_dict(lowered)
        if cost and cost.get("bytes accessed") is not None:
            pc.lowered_bytes_accessed = float(cost["bytes accessed"])
        if level == "compiled":
            compiled = lowered.compile()
            # compiled cost_analysis reflects the optimized HLO; prefer it
            cost = _cost_dict(compiled) or cost
            try:
                mem = compiled.memory_analysis()
            except Exception:
                mem = None
            if mem is not None:
                pc.argument_bytes = int(
                    getattr(mem, "argument_size_in_bytes", 0) or 0)
                pc.output_bytes = int(
                    getattr(mem, "output_size_in_bytes", 0) or 0)
                pc.temp_bytes = int(
                    getattr(mem, "temp_size_in_bytes", 0) or 0)
                pc.generated_code_bytes = int(
                    getattr(mem, "generated_code_size_in_bytes", 0) or 0)
                pc.peak_hbm_bytes = _peak_from_memory_analysis(mem)
        if cost:
            if cost.get("flops") is not None:
                pc.flops = float(cost["flops"])
            if cost.get("bytes accessed") is not None:
                pc.bytes_accessed = float(cost["bytes accessed"])
    except Exception as e:                       # pragma: no cover - backend
        log.warning("costmodel: capture of %s failed: %s: %s",
                    fn, type(e).__name__, str(e)[:200])
        return None
    with _lock:
        _costs[fn] = pc
    _set_gauges(pc)
    events.emit("program_cost", **pc.to_event_fields())
    return pc


# ----------------------------------------------------------------------
# Live device-memory watermarks
def device_memory_stats() -> dict | None:
    """{"bytes_in_use", "peak_bytes_in_use", ...} for the first local
    device, or None where the backend exposes no allocator stats (CPU)."""
    try:
        import jax
        stats = jax.local_devices()[0].memory_stats()
    except Exception:
        return None
    if not stats:
        return None
    return dict(stats)


def record_hbm_watermark(**context: Any) -> dict | None:
    """Emit one ``hbm_watermark`` event + refresh the HBM gauges from live
    allocator stats. Returns the stats, or None (silently) on backends
    without ``memory_stats()`` — per-iteration callers need no guard."""
    stats = device_memory_stats()
    if stats is None:
        return None
    in_use = stats.get("bytes_in_use")
    peak = stats.get("peak_bytes_in_use")
    reg = instruments.registry()
    if in_use is not None:
        reg.gauge("hbm_bytes_in_use").set(in_use)
    if peak is not None:
        reg.gauge("hbm_live_peak_bytes").set(peak)
        best = hbm_peak_bytes()
        if best is not None:
            reg.gauge("hbm_peak_bytes").set(best)
    events.emit("hbm_watermark", bytes_in_use=in_use, peak_bytes=peak,
                **context)
    return stats


def hbm_peak_bytes() -> int | None:
    """Best-known peak HBM: max of the static per-program accounting and
    the live allocator watermark. None when neither source has data."""
    peaks = [pc.peak_hbm_bytes for pc in costs().values()
             if pc.peak_hbm_bytes is not None]
    live = device_memory_stats()
    if live and live.get("peak_bytes_in_use") is not None:
        peaks.append(int(live["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


# ----------------------------------------------------------------------
# Peaks: the MFU / roofline denominators
def _peaks_for(device_kind: str) -> dict | None:
    """The table row of ``device_kind``; None for a CPU; raises on any
    other kind that is not in the table (an error, not a default)."""
    if device_kind.lower() == "cpu":
        return None
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"device_kind {device_kind!r} is not in costmodel.DEVICE_PEAKS "
            f"({sorted(DEVICE_PEAKS)}); add its published peaks with their "
            f"source before computing a utilization on it") from None


def peak_flops(device_kind: str,
               dtype: str = "bfloat16") -> tuple[float | None, str]:
    """(peak FLOP/s, source) for MFU. ``None`` — not measured — on a CPU
    and for a dtype whose peak the source does not state."""
    row = _peaks_for(device_kind)
    if row is None:
        return None, "not measured"
    return row["flops"].get(dtype), row["source"]


def peak_bytes_per_s(device_kind: str) -> tuple[float | None, str]:
    """(peak HBM bytes/s, source) for the bandwidth roofline axis."""
    row = _peaks_for(device_kind)
    if row is None:
        return None, "not measured"
    return row["bytes_per_s"], row["source"]


def roofline(flops: float | None, bytes_accessed: float | None,
             seconds: float, device_kind: str,
             dtype: str = "bfloat16") -> dict | None:
    """Achieved-vs-peak utilization on both roofline axes.

    Returns {"achieved_flops_per_s", "flops_utilization",
    "achieved_bytes_per_s", "bandwidth_utilization", "bound",
    "peak_flops", "peak_bytes_per_s", "peak_source"} — ``bound`` names
    whichever axis is closer to its peak (the binding resource). None on
    a CPU: a utilization needs a peak, and a CPU run has none.
    """
    if seconds <= 0 or (flops is None and bytes_accessed is None):
        return None
    pf, src = peak_flops(device_kind, dtype)
    pb, _ = peak_bytes_per_s(device_kind)
    if pb is None:
        return None
    out: dict[str, Any] = {"peak_flops": pf, "peak_bytes_per_s": pb,
                           "peak_source": src}
    fu = bu = None
    if flops is not None:
        out["achieved_flops_per_s"] = flops / seconds
        if pf is not None:
            fu = out["flops_utilization"] = round(flops / seconds / pf, 6)
    if bytes_accessed is not None:
        out["achieved_bytes_per_s"] = bytes_accessed / seconds
        bu = out["bandwidth_utilization"] = round(
            bytes_accessed / seconds / pb, 6)
    out["bound"] = ("compute" if (fu or 0) >= (bu or 0) else "memory")
    return out


# ----------------------------------------------------------------------
# Model-level FLOP counting
def forward_flops_per_example(exp) -> float:
    """Forward FLOPs per example of an Experiment's model, preferring
    XLA's cost analysis of the compiled single-model forward (exact for
    convs, where the dense 2-FLOPs-per-param rule undercounts by orders
    of magnitude). Falls back to the dense analytic rule."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    batch = min(exp.cfg.batch_size, 256)
    try:
        # exp.ds is always populated (exp.x is None under stream_data)
        x1 = jnp.zeros((batch, *exp.ds.feature_shape), exp.ds.x.dtype)
        compiled = jax.jit(exp.pool.apply).lower(
            exp.pool.slot(0), x1).compile()
        cost = _cost_dict(compiled)
        return float(cost["flops"]) / batch
    except Exception:
        n_params = sum(int(np.prod(l.shape[1:]))   # leading M axis excluded
                       for l in jax.tree_util.tree_leaves(exp.pool.params))
        return 2.0 * n_params


def round_flops(exp) -> tuple[float, str]:
    """(FLOPs per communication round, source) for an Experiment.

    Prefers the captured cost of the program that actually runs the
    round: the fused ``train_iteration_eval`` executes ``comm_round``
    rounds (plus its in-program evals) per dispatch; ``train_round``
    executes one. Falls back to the analytic estimate (forward cost
    model × the round's step arithmetic) when no program was captured.
    """
    pc = get("train_iteration_eval")
    if pc is not None and pc.flops:
        return pc.flops / max(exp.cfg.comm_round, 1), "cost_analysis"
    pc = get("train_round")
    if pc is not None and pc.flops:
        # eval programs run separately on this path; amortise them in
        eval_pc = get("acc_matrix")
        per_eval = (2 * eval_pc.flops if eval_pc is not None and eval_pc.flops
                    else 0.0)
        return (pc.flops + per_eval / max(exp.cfg.frequency_of_the_test, 1),
                "cost_analysis")
    return analytic_round_flops(exp), "analytic"


def round_bytes(exp) -> float | None:
    """Bytes accessed per communication round from the captured round
    program, or None when nothing was captured."""
    pc = get("train_iteration_eval")
    if pc is not None and pc.bytes_accessed:
        return pc.bytes_accessed / max(exp.cfg.comm_round, 1)
    pc = get("train_round")
    if pc is not None and pc.bytes_accessed:
        return pc.bytes_accessed
    return None


def analytic_round_flops(exp) -> float:
    """Analytic round-FLOPs estimate: backward ≈ 2× forward, so a train
    step costs ~3× the forward. Per round: M × C local trainers each run
    ``epochs`` SGD steps on a ``batch_size`` batch; eval matrices add
    M × C full-step inferences every ``frequency_of_the_test`` rounds
    (amortised in)."""
    cfg, ds = exp.cfg, exp.ds
    fpe = forward_flops_per_example(exp)
    M, C = exp.pool.num_models, cfg.device_clients
    train = M * C * cfg.epochs * cfg.batch_size * fpe * 3
    eval_amortised = (M * C * ds.samples_per_step * fpe
                      / max(cfg.frequency_of_the_test, 1))
    return float(train + eval_amortised)
