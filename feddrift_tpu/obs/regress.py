"""Perf-regression gate over bench.py artifacts.

The comparator for two ``bench.py`` snapshots, runnable in CI:

    python -m feddrift_tpu regress <bench.json> --baseline <bench_old.json>

Accepts both raw ``bench.py`` stdout (a JSON object / last JSON line of a
capture) and the driver-snapshot wrapper format (the bench object under
``"parsed"``). Compares the
metrics a throughput regression shows up in — rounds/s, wall seconds,
steady-state XLA compile counts, final test accuracy — and exits nonzero
iff any regresses past its threshold, printing a delta table either way.

Thresholds are *noise-aware* by construction: every limit is explicit,
relative where the metric scales (throughput, wall) and absolute where
it does not (accuracy, compile counts), with defaults sized for a noisy
1-core CI host. A metric missing from either side is reported as
``skip``, never a failure — older artifacts (no ``instruments`` key) and
``--smoke`` runs (no baselines) stay comparable on the metrics they do
carry. ``wall_s`` is only compared when both runs measured the same
number of rounds (otherwise wall scales with work, not speed).
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Any

# (flag, default) — relative for throughput/wall, absolute for the rest
DEFAULT_TOL = {
    "rounds": 0.25,      # fail if rounds/s < baseline * (1 - tol)
    "wall": 0.30,        # fail if wall_s > baseline * (1 + tol)
    "acc": 0.02,         # fail if final_test_acc < baseline - tol
    "compiles": 0.0,     # fail if steady-state compiles > baseline + tol
    "bytes": 0.25,       # fail if bytes_per_round > baseline * (1 + tol)
    "host_overhead": 0.10,   # fail if host_overhead_frac > baseline + tol
    "p99": 0.75,         # fail if round_wall_p99_s > baseline * (1 + tol)
    "precision_acc": 0.05,   # fail if a reduced-precision row's accuracy
                             # < this run's own f32 row - tol
    "quality_acc": 0.05,     # fail if the streaming live-accuracy estimate
                             # drifts further than this from the offline
                             # oracle on the same labeled stream
    "secure_wall": 1.0,      # fail if the secure-agg engine wall/round >
                             # baseline * (1 + tol) — host-side numpy on
                             # shared CI, so the ceiling is generous
    "hostscale_exp": 0.2,    # fail if a fitted host-plane scaling exponent
                             # (host-seconds/round or bytes vs P, log-log
                             # slope) > baseline + tol — absolute headroom
                             # sized for fit noise on short sweeps
}


def load_bench(path: str) -> dict:
    """Load a bench artifact: raw bench.py output, a mixed-output capture
    (last parseable JSON line wins), or a driver wrapper (bench object
    under "parsed", e.g. BENCH_r10.json)."""
    with open(path) as f:
        text = f.read()
    try:
        d = json.loads(text)
    except json.JSONDecodeError:
        d = None
        for line in reversed(text.strip().splitlines()):
            try:
                d = json.loads(line)
                break
            except json.JSONDecodeError:
                continue
        if d is None:
            raise ValueError(f"{path}: no JSON object found")
    if not isinstance(d, dict):
        raise ValueError(f"{path}: expected a JSON object")
    if "parsed" in d and isinstance(d["parsed"], dict):
        d = d["parsed"]                # driver wrapper
    return d


def _compile_counts(bench: dict) -> tuple[float | None, float | None]:
    """(compiles, recompiles) summed over programs from the instruments
    snapshot, or (None, None) when the artifact predates instruments."""
    inst = bench.get("instruments")
    if not isinstance(inst, dict):
        return None, None
    comp = sum(v for k, v in inst.items()
               if k.startswith("jit_compiles") and isinstance(v, (int, float)))
    rec = sum(v for k, v in inst.items()
              if k.startswith("jit_recompiles") and isinstance(v, (int, float)))
    return comp, rec


def extract_metrics(bench: dict) -> dict[str, float | None]:
    comp, rec = _compile_counts(bench)
    return {
        "rounds_per_s": bench.get("value"),
        "wall_s": bench.get("wall_s"),
        "rounds": bench.get("rounds"),
        "final_test_acc": bench.get("final_test_acc"),
        "jit_compiles": comp,
        "jit_recompiles": rec,
        "host_overhead_frac": bench.get("host_overhead_frac"),
        "round_wall_p99_s": bench.get("round_wall_p99_s"),
    }


def compare(candidate: dict, baseline: dict,
            tol: dict[str, float] | None = None) -> list[dict[str, Any]]:
    """Delta rows, one per gated metric: {"metric", "baseline",
    "candidate", "delta_pct", "limit", "status"} with status ∈
    ok | regress | skip."""
    tol = {**DEFAULT_TOL, **(tol or {})}
    c, b = extract_metrics(candidate), extract_metrics(baseline)
    rows: list[dict[str, Any]] = []

    def row(metric, bv, cv, limit, regressed, note=None):
        r: dict[str, Any] = {"metric": metric, "baseline": bv,
                             "candidate": cv, "limit": limit,
                             "status": "regress" if regressed else "ok"}
        if bv not in (None, 0) and cv is not None:
            r["delta_pct"] = round(100.0 * (cv - bv) / bv, 2)
        if note:
            r["note"] = note
        return r

    def skip(metric, note):
        rows.append({"metric": metric, "baseline": b.get(metric),
                     "candidate": c.get(metric), "status": "skip",
                     "note": note})

    # throughput: higher is better, relative tolerance
    if b["rounds_per_s"] is None or c["rounds_per_s"] is None:
        skip("rounds_per_s", "missing from one side")
    else:
        floor = b["rounds_per_s"] * (1.0 - tol["rounds"])
        rows.append(row("rounds_per_s", b["rounds_per_s"], c["rounds_per_s"],
                        f">= {floor:.3f}", c["rounds_per_s"] < floor))

    # wall: lower is better; comparable only for equal measured rounds
    if b["wall_s"] is None or c["wall_s"] is None:
        skip("wall_s", "missing from one side")
    elif b["rounds"] != c["rounds"]:
        skip("wall_s", f"rounds differ ({b['rounds']} vs {c['rounds']})")
    else:
        ceil = b["wall_s"] * (1.0 + tol["wall"])
        rows.append(row("wall_s", b["wall_s"], c["wall_s"],
                        f"<= {ceil:.3f}", c["wall_s"] > ceil))

    # accuracy: higher is better, absolute tolerance
    if b["final_test_acc"] is None or c["final_test_acc"] is None:
        skip("final_test_acc", "missing from one side")
    else:
        floor = b["final_test_acc"] - tol["acc"]
        rows.append(row("final_test_acc", b["final_test_acc"],
                        c["final_test_acc"], f">= {floor:.4f}",
                        c["final_test_acc"] < floor))

    # host-overhead ceiling: lower is better, absolute tolerance (a
    # fraction in [0, 1] — relative deltas would blow up near zero).
    # Gates the critical-path attribution loop: work moved off the
    # device (slower dispatch, host-side stalls) raises this before it
    # shows up in wall clock on a fast accelerator.
    if (b["host_overhead_frac"] is None
            or c["host_overhead_frac"] is None):
        skip("host_overhead_frac", "missing from one side")
    else:
        ceil = b["host_overhead_frac"] + tol["host_overhead"]
        rows.append(row("host_overhead_frac", b["host_overhead_frac"],
                        c["host_overhead_frac"], f"<= {ceil:.4f}",
                        c["host_overhead_frac"] > ceil))

    # tail latency ceiling: lower is better, relative tolerance sized for
    # p99-of-few-hundred-samples noise on a shared host. Artifacts that
    # predate the streaming quantile sketch skip, never fail.
    if (b["round_wall_p99_s"] is None or c["round_wall_p99_s"] is None):
        skip("round_wall_p99_s", "missing from one side")
    else:
        ceil = b["round_wall_p99_s"] * (1.0 + tol["p99"])
        rows.append(row("round_wall_p99_s", b["round_wall_p99_s"],
                        c["round_wall_p99_s"], f"<= {ceil:.4f}",
                        c["round_wall_p99_s"] > ceil))

    # steady-state compile counts: lower is better, absolute tolerance
    for metric in ("jit_compiles", "jit_recompiles"):
        if b[metric] is None or c[metric] is None:
            skip(metric, "no instruments snapshot")
        else:
            ceil = b[metric] + tol["compiles"]
            rows.append(row(metric, b[metric], c[metric],
                            f"<= {ceil:g}", c[metric] > ceil))

    # population-scaling axis (bench.py --popscale; POPSCALE artifacts):
    # rounds/s per population point under the throughput tolerance, and
    # steady-state recompiles as an ABSOLUTE zero gate — growing the
    # population at fixed cohort must never change an XLA program shape.
    cps, bps = candidate.get("popscale"), baseline.get("popscale")
    if isinstance(cps, list) and isinstance(bps, list):
        by_pop = {e.get("population"): e for e in bps if isinstance(e, dict)}
        for e in cps:
            if not isinstance(e, dict):
                continue
            p = e.get("population")
            be = by_pop.get(p)
            if be is None:
                skip(f"popscale[{p}]", "population point missing in baseline")
                continue
            bv, cv = be.get("rounds_per_sec"), e.get("rounds_per_sec")
            if bv and cv:
                floor = bv * (1.0 - tol["rounds"])
                rows.append(row(f"popscale[{p}].rounds_per_s", bv, cv,
                                f">= {floor:.3f}", cv < floor))
            rec = e.get("steady_recompiles")
            if rec is not None:
                rows.append(row(f"popscale[{p}].steady_recompiles",
                                be.get("steady_recompiles"), rec, "== 0",
                                rec > 0,
                                note="compile-count invariance over "
                                     "population size"))
    elif isinstance(bps, list):
        skip("popscale", "candidate lacks the popscale axis")

    # host-plane scaling axis (bench.py --hostscale; HOSTSCALE artifacts):
    # the ISSUE-19 gate on the dense-O(P) host behaviors. Per population
    # point: rounds/s under the throughput tolerance and steady-state
    # recompiles as an ABSOLUTE zero gate (the ledger + profiler are pure
    # host work — enabling them must not mint programs). Then the fitted
    # log-log scaling exponents per subsystem (host-seconds/round vs P)
    # and per structure (bytes vs P) under an absolute +tol["hostscale_exp"]
    # headroom, and bytes/client at the largest P under the bytes ceiling —
    # the named numbers the ROADMAP item-2 refactor must beat.
    chs, bhs = candidate.get("hostscale"), baseline.get("hostscale")
    if isinstance(chs, dict) and isinstance(bhs, dict):
        b_rows = {e.get("population"): e
                  for e in (bhs.get("rows") or []) if isinstance(e, dict)}
        for e in (chs.get("rows") or []):
            if not isinstance(e, dict):
                continue
            p = e.get("population")
            be = b_rows.get(p)
            if be is None:
                skip(f"hostscale[{p}]",
                     "population point missing in baseline")
                continue
            bv, cv = be.get("rounds_per_sec"), e.get("rounds_per_sec")
            if bv and cv:
                floor = bv * (1.0 - tol["rounds"])
                rows.append(row(f"hostscale[{p}].rounds_per_s", bv, cv,
                                f">= {floor:.3f}", cv < floor))
            rec = e.get("steady_recompiles")
            if rec is not None:
                rows.append(row(f"hostscale[{p}].steady_recompiles",
                                be.get("steady_recompiles"), rec, "== 0",
                                rec > 0,
                                note="ledger + profiler are pure host "
                                     "work"))
        for axis, label in (("exp_seconds", "s/round"),
                            ("exp_bytes", "bytes")):
            b_exp = bhs.get(axis) or {}
            for sub, cv in sorted((chs.get(axis) or {}).items()):
                bv = b_exp.get(sub)
                name = f"hostscale.{axis}[{sub}]"
                if cv is None or bv is None:
                    skip(name, "exponent unfit on one side")
                    continue
                ceil = bv + tol["hostscale_exp"]
                rows.append(row(name, bv, cv, f"<= {ceil:.3f}", cv > ceil,
                                note=f"log-log {label} vs P slope"))
        b_bpc = bhs.get("bytes_per_client") or {}
        for s, cv in sorted((chs.get("bytes_per_client") or {}).items()):
            bv = b_bpc.get(s)
            name = f"hostscale.bytes_per_client[{s}]"
            if bv is None:
                skip(name, "structure missing in baseline")
                continue
            ceil = bv * (1.0 + tol["bytes"])
            rows.append(row(name, bv, cv, f"<= {ceil:.1f}", cv > ceil,
                            note="host bytes per registered client at "
                                 "max P"))
    elif isinstance(bhs, dict):
        skip("hostscale", "candidate lacks the hostscale axis")

    # multi-iteration megastep axis (bench.py --megastep; MEGASTEP
    # artifacts): rounds/s per K point under the throughput tolerance,
    # steady-state recompiles as an ABSOLUTE zero gate (fusing more
    # iterations must never grow the XLA program count — K is a static
    # arg, one program per K, compiled in warm-up), and a host-overhead
    # ceiling at every K>1 STRICTLY below the same artifact's K=1 row —
    # the whole point of the megastep is amortizing the host round-trip,
    # so a K>1 row with K=1-level host overhead is a regression even if
    # throughput still clears its floor.
    # Rows are keyed per (variant, K): legacy artifacts (MEGASTEP_r10)
    # carry no "variant" field and keep their bare megastep[{k}] keys
    # (treated as the "dense" variant); composed rows render as
    # megastep[{variant}:{k}]. The pop_hier variant additionally carries
    # an ABSOLUTE >= 2x speedup-vs-own-K=1 gate — the ISSUE-13 acceptance
    # bar for fusing population cohorts + hierarchy + chaos, immune to a
    # baseline that itself regressed.
    cms, bms = candidate.get("megastep"), baseline.get("megastep")
    if isinstance(cms, list) and isinstance(bms, list):
        def _vk(e):
            return (e.get("variant") or "dense", e.get("megastep_k"))

        def _key(variant, k):
            return (f"megastep[{k}]" if variant == "dense"
                    else f"megastep[{variant}:{k}]")

        by_vk = {_vk(e): e for e in bms if isinstance(e, dict)}
        k1_by_variant = {_vk(e)[0]: e for e in cms if isinstance(e, dict)
                         and e.get("megastep_k") == 1}
        for e in cms:
            if not isinstance(e, dict):
                continue
            variant, k = _vk(e)
            name = _key(variant, k)
            be = by_vk.get((variant, k))
            if be is None:
                skip(name, "variant/K point missing in baseline")
                continue
            bv, cv = be.get("rounds_per_sec"), e.get("rounds_per_sec")
            if bv and cv:
                floor = bv * (1.0 - tol["rounds"])
                rows.append(row(f"{name}.rounds_per_s", bv, cv,
                                f">= {floor:.3f}", cv < floor))
            rec = e.get("steady_recompiles")
            if rec is not None:
                rows.append(row(f"{name}.steady_recompiles",
                                be.get("steady_recompiles"), rec, "== 0",
                                rec > 0,
                                note="compile-count invariance over K"))
            hof = e.get("host_overhead_frac")
            hof1 = (k1_by_variant.get(variant)
                    or {}).get("host_overhead_frac")
            if k and k > 1 and hof is not None and hof1 is not None:
                rows.append(row(f"{name}.host_overhead_frac",
                                be.get("host_overhead_frac"), hof,
                                f"< {hof1:.4f}", hof >= hof1,
                                note="must beat this run's own-variant "
                                     "K=1 row"))
            sp = e.get("speedup_vs_k1")
            if variant == "pop_hier" and k and k > 1 and sp is not None:
                rows.append(row(f"{name}.speedup_vs_k1",
                                be.get("speedup_vs_k1"), sp, ">= 2",
                                sp < 2.0,
                                note="absolute composed-fusion floor vs "
                                     "own K=1"))
    elif isinstance(bms, list):
        skip("megastep", "candidate lacks the megastep axis")

    # two-tier wire axis (bench.py --hierarchy; COMM artifacts): broker
    # bytes/round per codec under the bytes ceiling, plus an ABSOLUTE
    # >= 3x reduction floor for every lossy codec — a codec that stops
    # compressing is a regression even if the baseline also regressed.
    ch, bh = candidate.get("hierarchy"), baseline.get("hierarchy")
    if isinstance(ch, list) and isinstance(bh, list):
        by_codec = {e.get("codec"): e for e in bh if isinstance(e, dict)}
        for e in ch:
            if not isinstance(e, dict):
                continue
            cd = e.get("codec")
            be = by_codec.get(cd)
            if be is None:
                skip(f"hierarchy[{cd}]", "codec missing in baseline")
                continue
            bv, cv = be.get("bytes_per_round"), e.get("bytes_per_round")
            if bv and cv:
                ceil = bv * (1.0 + tol["bytes"])
                rows.append(row(f"hierarchy[{cd}].bytes_per_round", bv, cv,
                                f"<= {ceil:.0f}", cv > ceil))
            ratio = e.get("ratio_vs_none")
            if cd != "none" and ratio is not None:
                rows.append(row(f"hierarchy[{cd}].ratio_vs_none",
                                be.get("ratio_vs_none"), ratio, ">= 3",
                                ratio < 3.0,
                                note="compression floor vs uncompressed"))
    elif isinstance(bh, list):
        skip("hierarchy", "candidate lacks the hierarchy axis")

    # end-to-end precision-policy axis (bench.py --precision; PRECISION
    # artifacts): one row per (variant, policy) from the paired sweep.
    # rounds/s under the throughput tolerance, steady-state recompiles as
    # an ABSOLUTE zero gate (a policy is one jit signature per program,
    # compiled in warm-up — never a per-round dtype lottery), accuracy of
    # every reduced-precision row within --tol-precision-acc of this
    # run's OWN f32 row (immune to a baseline that itself drifted), and
    # ABSOLUTE ceilings on the bf16_mixed cost-model/wire ratios — the
    # ISSUE-15 acceptance bars: program_bytes_accessed <= 0.60x and wire
    # bytes/round <= 0.55x of the paired f32 row. Rows are keyed
    # precision[{variant}:{policy}] so future model variants never
    # collide with the resnet rows.
    cpr, bpr = candidate.get("precision"), baseline.get("precision")
    if isinstance(cpr, list) and isinstance(bpr, list):
        def _vp(e):
            return (e.get("variant") or "resnet", e.get("policy"))

        by_vp = {_vp(e): e for e in bpr if isinstance(e, dict)}
        f32_by_variant = {_vp(e)[0]: e for e in cpr if isinstance(e, dict)
                          and e.get("policy") == "f32"}
        for e in cpr:
            if not isinstance(e, dict):
                continue
            variant, pol = _vp(e)
            name = f"precision[{variant}:{pol}]"
            be = by_vp.get((variant, pol))
            if be is None:
                skip(name, "variant/policy point missing in baseline")
                continue
            bv, cv = be.get("rounds_per_sec"), e.get("rounds_per_sec")
            if bv and cv:
                floor = bv * (1.0 - tol["rounds"])
                rows.append(row(f"{name}.rounds_per_s", bv, cv,
                                f">= {floor:.3f}", cv < floor))
            rec = e.get("steady_recompiles")
            if rec is not None:
                rows.append(row(f"{name}.steady_recompiles",
                                be.get("steady_recompiles"), rec, "== 0",
                                rec > 0,
                                note="one program per policy, compiled "
                                     "in warm-up"))
            acc = e.get("final_test_acc")
            acc32 = (f32_by_variant.get(variant)
                     or {}).get("final_test_acc")
            if pol != "f32" and acc is not None and acc32 is not None:
                floor = acc32 - tol["precision_acc"]
                rows.append(row(f"{name}.final_test_acc",
                                be.get("final_test_acc"), acc,
                                f">= {floor:.4f}", acc < floor,
                                note="vs this run's own f32 row"))
            br = e.get("bytes_accessed_ratio")
            if pol == "bf16_mixed" and br is not None:
                rows.append(row(f"{name}.bytes_accessed_ratio",
                                be.get("bytes_accessed_ratio"), br,
                                "<= 0.6", br > 0.60,
                                note="absolute HBM-traffic ceiling vs "
                                     "own f32 row"))
            wr = e.get("wire_bytes_ratio")
            if pol == "bf16_mixed" and wr is not None:
                rows.append(row(f"{name}.wire_bytes_ratio",
                                be.get("wire_bytes_ratio"), wr,
                                "<= 0.55", wr > 0.55,
                                note="absolute wire-bytes ceiling vs "
                                     "own f32 row"))
    elif isinstance(bpr, list):
        skip("precision", "candidate lacks the precision axis")

    # serving read-path axis (bench.py --serve; SERVE artifacts): one row
    # per (mode, max-bucket) point — in-process closed-loop rows plus the
    # mode="socket" frontend row (HTTP plane, 2 replicas, bounded
    # admission; carries the open-loop knee ladder and its gated
    # shed-rate bound).
    # requests/s under the throughput tolerance, request p99 under the
    # tail-latency tolerance, steady-state recompiles as an ABSOLUTE zero
    # gate (buckets are compiled in warm-up; mixed-cluster traffic must
    # never mint a new XLA program), plus an ABSOLUTE >= 3x floor on the
    # best batched speedup-vs-unbatched — micro-batching that stops paying
    # for itself is a regression even if the baseline also regressed.
    # Rows are keyed serve[{mode}:b{bucket}] so an unbatched bucket=1 row
    # and a batched row never collide across variants.
    csv_, bsv = candidate.get("serve"), baseline.get("serve")
    if isinstance(csv_, list) and isinstance(bsv, list):
        def _mb(e):
            return (e.get("mode") or "batched", e.get("bucket"))

        by_mb = {_mb(e): e for e in bsv if isinstance(e, dict)}
        best_speedup = None
        for e in csv_:
            if not isinstance(e, dict):
                continue
            mode, bucket = _mb(e)
            name = f"serve[{mode}:b{bucket}]"
            sp = e.get("speedup_vs_unbatched")
            if mode == "batched" and sp is not None:
                best_speedup = sp if best_speedup is None \
                    else max(best_speedup, sp)
            be = by_mb.get((mode, bucket))
            if be is None:
                skip(name, "mode/bucket point missing in baseline")
                continue
            bv, cv = be.get("requests_per_s"), e.get("requests_per_s")
            if bv and cv:
                floor = bv * (1.0 - tol["rounds"])
                rows.append(row(f"{name}.requests_per_s", bv, cv,
                                f">= {floor:.1f}", cv < floor))
            bp, cp = be.get("p99_ms"), e.get("p99_ms")
            if bp and cp:
                ceil = bp * (1.0 + tol["p99"])
                rows.append(row(f"{name}.p99_ms", bp, cp,
                                f"<= {ceil:.3f}", cp > ceil))
            rec = e.get("steady_recompiles")
            if rec is not None:
                rows.append(row(f"{name}.steady_recompiles",
                                be.get("steady_recompiles"), rec, "== 0",
                                rec > 0,
                                note="program invariance under "
                                     "mixed-cluster traffic"))
            sr = e.get("shed_rate")
            if mode == "socket" and sr is not None:
                # ABSOLUTE bound on the sub-knee open-loop point: a
                # frontend shedding comfortably below its own measured
                # capacity is misconfigured admission, regardless of
                # what the baseline did
                rows.append(row(f"{name}.shed_rate",
                                be.get("shed_rate"), sr, "<= 0.05",
                                sr > 0.05,
                                note="open-loop shed rate at 0.5x "
                                     "measured capacity"))
        if best_speedup is not None:
            bbest = [e.get("speedup_vs_unbatched") for e in bsv
                     if isinstance(e, dict)
                     and e.get("speedup_vs_unbatched") is not None]
            rows.append(row("serve.best_speedup_vs_unbatched",
                            max(bbest) if bbest else None, best_speedup,
                            ">= 3", best_speedup < 3.0,
                            note="absolute micro-batching floor vs "
                                 "this run's own unbatched row"))
    elif isinstance(bsv, list):
        skip("serve", "candidate lacks the serve axis")

    # model-quality axis (bench.py --quality; QUALITY artifacts): the
    # seeded drifting-traffic serve bench with live label joins and two
    # canaried merges (one good, one deliberately wrong). Gates are
    # mostly ABSOLUTE against this run's own rows — the acceptance bars,
    # immune to a baseline that itself regressed: streaming accuracy
    # within --tol-quality-acc of the offline oracle on the same stream,
    # the good merge canary-committed and the corrupted one rolled back,
    # zero rollbacks outside the deliberate corruption, shadow-on
    # throughput >= 0.95x shadow-off (the <5% duplicate-execute budget),
    # and zero steady-state recompiles (shadow forwards replay warm
    # signatures). p99/requests-per-s ride the usual relative tolerances
    # when the baseline carries the axis.
    cq, bq = candidate.get("quality"), baseline.get("quality")
    if isinstance(cq, dict):
        bqd = bq if isinstance(bq, dict) else {}
        gap = cq.get("live_oracle_gap")
        if gap is not None:
            rows.append(row("quality.live_oracle_gap",
                            bqd.get("live_oracle_gap"), gap,
                            f"<= {tol['quality_acc']:.4f}",
                            gap > tol["quality_acc"],
                            note="streaming estimate vs offline oracle "
                                 "on the same labeled stream"))
        gm = cq.get("good_merge_committed")
        if gm is not None:
            rows.append(row("quality.good_merge_committed",
                            bqd.get("good_merge_committed"), gm, "== 1",
                            gm != 1, note="clean merge must canary-commit"))
        bm = cq.get("bad_merge_rolled_back")
        if bm is not None:
            rows.append(row("quality.bad_merge_rolled_back",
                            bqd.get("bad_merge_rolled_back"), bm, "== 1",
                            bm != 1,
                            note="corrupted merge must canary-rollback"))
        cr = cq.get("clean_canary_rollbacks")
        if cr is not None:
            rows.append(row("quality.clean_canary_rollbacks",
                            bqd.get("clean_canary_rollbacks"), cr, "== 0",
                            cr > 0,
                            note="no false rollbacks on clean traffic"))
        sr = cq.get("shadow_overhead_ratio")
        if sr is not None:
            rows.append(row("quality.shadow_overhead_ratio",
                            bqd.get("shadow_overhead_ratio"), sr,
                            ">= 0.95", sr < 0.95,
                            note="shadow-on rps vs own shadow-off rps"))
        rec = cq.get("steady_recompiles")
        if rec is not None:
            rows.append(row("quality.steady_recompiles",
                            bqd.get("steady_recompiles"), rec, "== 0",
                            rec > 0,
                            note="shadow forwards replay warm signatures"))
        bp, cp = bqd.get("p99_ms"), cq.get("p99_ms")
        if bp and cp:
            ceil = bp * (1.0 + tol["p99"])
            rows.append(row("quality.p99_ms", bp, cp,
                            f"<= {ceil:.3f}", cp > ceil))
        bv, cv = bqd.get("requests_per_s"), cq.get("requests_per_s")
        if bv and cv:
            floor = bv * (1.0 - tol["rounds"])
            rows.append(row("quality.requests_per_s", bv, cv,
                            f">= {floor:.1f}", cv < floor))
    elif isinstance(bq, dict):
        skip("quality", "candidate lacks the quality axis")

    # secure-aggregation axis (bench.py --secure; SECAGG artifacts): rows
    # keyed secure[{mode}:{point}]. bytes_per_round under the bytes
    # ceiling (shamir is a real TCP wire measurement, turbo static frame
    # accounting — both deterministic for a fixed cohort/dim), engine
    # wall/round under the secure_wall ceiling (host-side numpy on shared
    # CI, hence the generous tolerance), and on the train rows an
    # ABSOLUTE zero gate on steady-state recompiles — the share protocol
    # runs on the host and must never mint a new XLA signature on the
    # otherwise-unchanged train program.
    cs, bs = candidate.get("secure"), baseline.get("secure")
    if isinstance(cs, list) and isinstance(bs, list):
        def _mp(e):
            return (e.get("mode"), e.get("point"))

        by_mp = {_mp(e): e for e in bs if isinstance(e, dict)}
        for e in cs:
            if not isinstance(e, dict):
                continue
            mode, point = _mp(e)
            name = f"secure[{mode}:{point}]"
            be = by_mp.get((mode, point))
            if be is None:
                skip(name, "mode/point missing in baseline")
                continue
            if point == "train":
                rec = e.get("steady_recompiles")
                if rec is not None:
                    rows.append(row(f"{name}.steady_recompiles",
                                    be.get("steady_recompiles"), rec,
                                    "== 0", rec > 0,
                                    note="secure round mode is host-side"))
                bv, cv = be.get("rounds_per_sec"), e.get("rounds_per_sec")
                if bv and cv:
                    floor = bv * (1.0 - tol["rounds"])
                    rows.append(row(f"{name}.rounds_per_sec", bv, cv,
                                    f">= {floor:.1f}", cv < floor))
                continue
            bv, cv = be.get("bytes_per_round"), e.get("bytes_per_round")
            if bv and cv:
                ceil = bv * (1.0 + tol["bytes"])
                rows.append(row(f"{name}.bytes_per_round", bv, cv,
                                f"<= {ceil:.0f}", cv > ceil))
            bw, cw = (be.get("wall_s_secure_per_round"),
                      e.get("wall_s_secure_per_round"))
            if bw and cw:
                ceil = bw * (1.0 + tol["secure_wall"])
                rows.append(row(f"{name}.wall_s_secure_per_round", bw, cw,
                                f"<= {ceil:.4g}", cw > ceil,
                                note="engine overhead ceiling"))
    elif isinstance(bs, list):
        skip("secure", "candidate lacks the secure axis")
    return rows


def render(rows: list[dict[str, Any]]) -> str:
    def fmt(v):
        if v is None:
            return "-"
        if isinstance(v, float):
            return f"{v:.4g}"
        return str(v)

    head = f"{'metric':<16} {'baseline':>10} {'candidate':>10} " \
           f"{'delta':>8} {'limit':>12}  status"
    lines = [head, "-" * len(head)]
    for r in rows:
        delta = (f"{r['delta_pct']:+.1f}%" if "delta_pct" in r else "-")
        status = r["status"].upper() if r["status"] == "regress" \
            else r["status"]
        note = f"  ({r['note']})" if r.get("note") else ""
        lines.append(f"{r['metric']:<16} {fmt(r.get('baseline')):>10} "
                     f"{fmt(r.get('candidate')):>10} {delta:>8} "
                     f"{fmt(r.get('limit')):>12}  {status}{note}")
    n_reg = sum(1 for r in rows if r["status"] == "regress")
    lines.append("")
    lines.append(f"{'REGRESSION' if n_reg else 'OK'}: "
                 f"{n_reg} regressed, "
                 f"{sum(1 for r in rows if r['status'] == 'ok')} ok, "
                 f"{sum(1 for r in rows if r['status'] == 'skip')} skipped")
    return "\n".join(lines)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="feddrift_tpu regress",
        description="compare a bench.py artifact against a baseline; "
                    "exit 1 on regression")
    ap.add_argument("candidate", help="bench JSON to gate")
    ap.add_argument("--baseline", required=True,
                    help="bench JSON to compare against (raw output or a "
                         "driver wrapper)")
    ap.add_argument("--tol-rounds", type=float, default=DEFAULT_TOL["rounds"],
                    help="relative rounds/s drop tolerated (default %(default)s)")
    ap.add_argument("--tol-wall", type=float, default=DEFAULT_TOL["wall"],
                    help="relative wall_s growth tolerated (default %(default)s)")
    ap.add_argument("--tol-acc", type=float, default=DEFAULT_TOL["acc"],
                    help="absolute final_test_acc drop tolerated "
                         "(default %(default)s)")
    ap.add_argument("--tol-compiles", type=float,
                    default=DEFAULT_TOL["compiles"],
                    help="absolute extra steady-state compiles tolerated "
                         "(default %(default)s)")
    ap.add_argument("--tol-bytes", type=float, default=DEFAULT_TOL["bytes"],
                    help="relative wire bytes/round growth tolerated "
                         "(default %(default)s)")
    ap.add_argument("--tol-host-overhead", type=float,
                    default=DEFAULT_TOL["host_overhead"],
                    help="absolute host_overhead_frac growth tolerated "
                         "(default %(default)s)")
    ap.add_argument("--tol-p99", type=float, default=DEFAULT_TOL["p99"],
                    help="relative round_wall_p99_s growth tolerated "
                         "(default %(default)s)")
    ap.add_argument("--tol-precision-acc", type=float,
                    default=DEFAULT_TOL["precision_acc"],
                    help="absolute accuracy drop tolerated for a reduced-"
                         "precision row vs its own run's f32 row "
                         "(default %(default)s)")
    ap.add_argument("--tol-quality-acc", type=float,
                    default=DEFAULT_TOL["quality_acc"],
                    help="absolute gap tolerated between the streaming "
                         "live-accuracy estimate and the offline oracle "
                         "on the same labeled stream (default %(default)s)")
    ap.add_argument("--tol-secure-wall", type=float,
                    default=DEFAULT_TOL["secure_wall"],
                    help="relative secure-agg engine wall/round growth "
                         "tolerated (default %(default)s)")
    ap.add_argument("--tol-hostscale-exp", type=float,
                    default=DEFAULT_TOL["hostscale_exp"],
                    help="absolute growth tolerated in a fitted host-plane "
                         "scaling exponent (default %(default)s)")
    ap.add_argument("--json", action="store_true", help="machine-readable")
    args = ap.parse_args(argv)

    try:
        candidate = load_bench(args.candidate)
        baseline = load_bench(args.baseline)
    except (OSError, ValueError) as e:
        print(f"regress: {e}", file=sys.stderr)
        return 2

    rows = compare(candidate, baseline,
                   tol={"rounds": args.tol_rounds, "wall": args.tol_wall,
                        "acc": args.tol_acc, "compiles": args.tol_compiles,
                        "bytes": args.tol_bytes,
                        "host_overhead": args.tol_host_overhead,
                        "p99": args.tol_p99,
                        "precision_acc": args.tol_precision_acc,
                        "quality_acc": args.tol_quality_acc,
                        "secure_wall": args.tol_secure_wall,
                        "hostscale_exp": args.tol_hostscale_exp})
    regressed = any(r["status"] == "regress" for r in rows)
    if args.json:
        print(json.dumps({"regressed": regressed, "rows": rows,
                          "candidate": args.candidate,
                          "baseline": args.baseline}, indent=2))
    else:
        print(f"candidate: {args.candidate}\nbaseline:  {args.baseline}\n")
        print(render(rows))
    return 1 if regressed else 0


if __name__ == "__main__":
    sys.exit(main())
