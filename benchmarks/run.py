"""Benchmark harness: one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows. A module failure — at
import or inside main() — prints its ERROR row and the suite
continues; the exit code is nonzero iff any module failed.

``--smoke`` runs tiny shapes so CI finishes in minutes: modules whose
``main`` accepts a ``smoke`` keyword get ``smoke=True``; the rest run
as-is (they are already CPU-sized).

``--out BENCH.json`` consolidates the headline numbers (fused-conv
speedup, pipeline bubble, fusion speedup + modeled HBM ratios,
placement bytes ratios) plus every module's returned dict into one
top-level JSON — uploaded as a CI artifact so the perf trajectory is
tracked across PRs.

``--baseline BENCH_BASELINE.json`` turns the smoke run into a
REGRESSION GATE: each headline key is compared against the checked-in
baseline with a per-key direction + relative tolerance (wall-clock
keys get loose tolerances, modeled/analytic keys tight ones); a
worse-than-tolerance value — or a baseline key that vanished — fails
the run. The delta table is printed, and appended to
``$GITHUB_STEP_SUMMARY`` as markdown when that env var is set (the CI
job summary).
"""
import argparse
import importlib
import inspect
import json
import os
import sys
import traceback

MODULES = ("balance_fig3", "planner_accuracy", "sparse_speedup",
           "conv_fused", "fusion", "throughput_tab4", "resources_tab2",
           "pipeline_cnn", "placement", "serving", "calibration")

# headline-key gate spec: direction ("higher"/"lower" is better) and
# relative tolerance. Wall-clock-derived keys are noisy on shared CI
# runners -> generous tolerance, regression-direction only; modeled /
# analytic keys are deterministic -> tight. A ZERO baseline has no
# relative scale, so the tolerance is applied as an ABSOLUTE bound
# there (e.g. pipeline_bubble_measured 0.0 -> 0.7 must still fail).
GATE = {
    "conv_fused_speedup_r50_3x3": ("higher", 0.50),
    "conv_fused_hbm_ratio_r50_3x3": ("higher", 0.05),
    "pipeline_bubble_measured": ("lower", 0.60),
    "pipeline_bubble_analytic": ("lower", 0.01),
    "pipeline_imbalance": ("lower", 0.10),
    # calibration: both derived from the checked-in tuning-cache FILE
    # (no wall clock at gate time) -> deterministic, tight. The cache
    # CONTENTS shift when regenerated on new hardware, so regeneration
    # re-baselines these.
    "pipeline_imbalance_measured": ("lower", 0.10),
    "planner_estimate_err_pct": ("lower", 0.25),
    "fusion_speedup_mbv1": ("higher", 0.50),
    "fusion_hbm_block_ratio_resnet50": ("higher", 0.05),
    "fusion_hbm_block_ratio_mobilenet_v1": ("higher", 0.05),
    "fusion_hbm_block_ratio_mobilenet_v2": ("higher", 0.05),
    "fusion_hbm_graph_ratio_resnet50": ("higher", 0.05),
    "fusion_hbm_graph_ratio_mobilenet_v1": ("higher", 0.05),
    "fusion_hbm_graph_ratio_mobilenet_v2": ("higher", 0.05),
    "placement_param_ratio_resnet50": ("lower", 0.05),
    "placement_param_ratio_mobilenet_v1": ("lower", 0.05),
    "placement_param_ratio_mobilenet_v2": ("lower", 0.05),
    # continuous serving: wall-clock im/s is noisy on shared runners
    # (regression-direction only, very loose); the steady bubble is
    # tick-count-derived — deterministic, tight
    "serving_throughput_imgs_per_s": ("higher", 0.90),
    "serving_steady_bubble": ("lower", 0.05),
    # request-latency tail: wall-clock (queueing + compute) on shared
    # runners — direction-only, very loose (a 2x p99 blowup still
    # fails; scheduler jitter does not)
    "serving_latency_p50_s": ("lower", 1.00),
    "serving_latency_p99_s": ("lower", 1.00),
    # batch-1 latency mode: wall-clock single-image round trips —
    # direction-only, very loose (same rationale as the tail above)
    "serving_latency_batch1_p50_s": ("lower", 1.00),
    "serving_latency_batch1_p99_s": ("lower", 1.00),
    # quantized placement: pure byte accounting over the same cut —
    # deterministic, tight. Must stay <= 0.5 (the ">= 2x cut" bar);
    # the analytic value is ~0.26 on sparse ResNet-50.
    "placement_param_ratio_int8": ("lower", 0.05),
    # cross-process recovery: kill-to-first-recovered-emit wall clock
    # (worker respawn + recompile dominate on shared runners) —
    # direction-only, very loose. Missed-heartbeat count stays
    # unGated: SIGKILL is usually detected via waitpid/EOF before any
    # heartbeat is missed, so its baseline is legitimately 0.
    "serving_recovery_s": ("lower", 1.00),
    # cross-host recovery: same wall-clock shape as the cross-process
    # number plus TCP re-dial + handshake + blob-cache resume —
    # direction-only, very loose
    "serving_recovery_net_s": ("lower", 1.00),
    # blob-by-hash transfer rate over loopback TCP: dominated by the
    # runner's memcpy/CRC bandwidth — noisy on shared runners, loose
    # higher-is-better (a 2x collapse still fails)
    "param_transfer_mb_s": ("higher", 0.50),
}


def _headline(modules: dict) -> dict:
    """Cross-PR trend numbers, pulled from the module result dicts.
    Missing modules (failed or returning None) yield nulls, never a
    crash — BENCH.json must materialize even on a partial run."""
    out = {}
    conv = modules.get("conv_fused") or {}
    if "r50_s1b0_c2" in conv:
        out["conv_fused_speedup_r50_3x3"] = conv["r50_s1b0_c2"]["speedup"]
        out["conv_fused_hbm_ratio_r50_3x3"] = \
            conv["r50_s1b0_c2"]["hbm_bytes_ratio"]
    pipe = modules.get("pipeline_cnn") or {}
    if pipe.get("points"):
        last = pipe["points"][-1]
        out["pipeline_bubble_measured"] = last["bubble_measured"]
        out["pipeline_bubble_analytic"] = last["bubble_analytic"]
        out["pipeline_imbalance"] = pipe.get("imbalance")
    fus = modules.get("fusion") or {}
    if fus.get("wallclock"):
        out["fusion_speedup_mbv1"] = fus["wallclock"]["speedup"]
    for arch, a in (fus.get("archs") or {}).items():
        out[f"fusion_hbm_block_ratio_{arch}"] = a["block_bytes_ratio"]
        out[f"fusion_hbm_graph_ratio_{arch}"] = a["graph_bytes_ratio"]
    plc = modules.get("placement") or {}
    for arch, a in (plc.get("archs") or {}).items():
        out[f"placement_param_ratio_{arch}"] = a["placed_ratio"]
    if "quantized" in plc:
        out["placement_param_ratio_int8"] = \
            plc["quantized"]["placement_param_ratio_int8"]
    cal = modules.get("calibration") or {}
    if "pipeline_imbalance_measured" in cal:
        out["pipeline_imbalance_measured"] = \
            cal["pipeline_imbalance_measured"]
        out["calibration_gain_pct"] = cal.get("calibration_gain_pct")
    acc = modules.get("planner_accuracy") or {}
    if "planner_estimate_err_pct" in acc:
        out["planner_estimate_err_pct"] = acc["planner_estimate_err_pct"]
        out["planner_estimate_err_analytic_pct"] = \
            acc.get("planner_estimate_err_analytic_pct")
    srv = modules.get("serving") or {}
    if "serving_throughput_imgs_per_s" in srv:
        out["serving_throughput_imgs_per_s"] = \
            srv["serving_throughput_imgs_per_s"]
        out["serving_steady_bubble"] = srv["serving_steady_bubble"]
        out["serving_latency_p50_s"] = srv.get("serving_latency_p50_s")
        out["serving_latency_p99_s"] = srv.get("serving_latency_p99_s")
    if "serving_latency_batch1_p50_s" in srv:
        out["serving_latency_batch1_p50_s"] = \
            srv["serving_latency_batch1_p50_s"]
        out["serving_latency_batch1_p99_s"] = \
            srv.get("serving_latency_batch1_p99_s")
    if "serving_recovery_s" in srv:
        out["serving_recovery_s"] = srv["serving_recovery_s"]
        out["serving_recovery_missed_heartbeats"] = \
            srv.get("serving_recovery_missed_heartbeats")
    if "serving_recovery_net_s" in srv:
        out["serving_recovery_net_s"] = srv["serving_recovery_net_s"]
    if "param_transfer_mb_s" in srv:
        out["param_transfer_mb_s"] = srv["param_transfer_mb_s"]
    return out


def compare_to_baseline(headline: dict, baseline: dict) -> tuple[list, bool]:
    """Per-key delta rows [(key, base, cur, delta%, status)] + overall
    pass/fail. A key present in the baseline but missing (or null) now
    is a regression (a module silently stopped reporting); a NEW key
    with no baseline is informational only."""
    rows, ok = [], True
    keys = sorted(set(baseline) | set(headline))
    for k in keys:
        base, cur = baseline.get(k), headline.get(k)
        if base is None:
            rows.append((k, base, cur, None, "new"))
            continue
        if cur is None:
            rows.append((k, base, cur, None, "MISSING"))
            ok = False
            continue
        if k not in GATE:
            # an ungated key has no declared direction — guessing one
            # would gate lower-is-better metrics backwards, so report
            # it informationally until a GATE entry is added
            delta = (cur - base) / abs(base) if base else None
            rows.append((k, base, cur, delta, "ungated"))
            continue
        direction, tol = GATE[k]
        if base:
            delta = (cur - base) / abs(base)
            worse = -delta if direction == "higher" else delta
        else:
            # zero baseline: relative delta is undefined — gate on the
            # absolute move instead (tol doubles as the absolute bound)
            delta = None
            worse = base - cur if direction == "higher" else cur - base
        status = "ok" if worse <= tol else "REGRESSED"
        if status == "REGRESSED":
            ok = False
        rows.append((k, base, cur, delta, status))
    return rows, ok


def _fmt(v):
    return "-" if v is None else (f"{v:.4g}" if isinstance(v, float)
                                  else str(v))


def render_delta_table(rows, markdown: bool = False) -> str:
    lines = []
    if markdown:
        lines.append("### Smoke benchmark gate\n")
        lines.append("| headline | baseline | current | delta | status |")
        lines.append("|---|---|---|---|---|")
        for k, base, cur, delta, status in rows:
            d = "-" if delta is None else f"{delta:+.1%}"
            mark = {"ok": "✅", "new": "🆕", "ungated": "ℹ️"}.get(
                status, "❌")
            lines.append(f"| {k} | {_fmt(base)} | {_fmt(cur)} | {d} "
                         f"| {mark} {status} |")
    else:
        for k, base, cur, delta, status in rows:
            d = "-" if delta is None else f"{delta:+.1%}"
            lines.append(f"# gate {status:>10}  {k}: {_fmt(base)} -> "
                         f"{_fmt(cur)} ({d})")
    return "\n".join(lines)


def run_gate(headline: dict, baseline_path: str) -> bool:
    with open(baseline_path) as f:
        baseline = json.load(f).get("headline", {})
    rows, ok = compare_to_baseline(headline, baseline)
    print(render_delta_table(rows), file=sys.stderr)
    summary = os.environ.get("GITHUB_STEP_SUMMARY")
    if summary:
        with open(summary, "a") as f:
            f.write(render_delta_table(rows, markdown=True) + "\n")
    if not ok:
        print("# benchmark gate FAILED (see table above)", file=sys.stderr)
    return ok


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes for CI")
    ap.add_argument("--out", default=None,
                    help="write consolidated headline JSON here")
    ap.add_argument("--baseline", default=None,
                    help="gate headline keys against this "
                         "BENCH_BASELINE.json (nonzero exit on "
                         "regression)")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    print("name,us_per_call,derived")
    failed = []
    module_results = {}
    for name in MODULES:
        try:
            fn = importlib.import_module(f"benchmarks.{name}").main
            if args.smoke and "smoke" in inspect.signature(fn).parameters:
                ret = fn(smoke=True)
            else:
                ret = fn()
            if isinstance(ret, dict):
                module_results[name] = ret
        except Exception:
            traceback.print_exc()
            print(f"benchmarks.{name},0,ERROR")
            failed.append(name)
    headline = _headline(module_results)
    if args.out:
        bench = {"smoke": args.smoke, "failed": failed,
                 "headline": headline,
                 "modules": module_results}
        with open(args.out, "w") as f:
            json.dump(bench, f, indent=1)
        print(f"# wrote {args.out}", file=sys.stderr)
    gate_ok = True
    if args.baseline:
        gate_ok = run_gate(headline, args.baseline)
    if failed:
        print(f"# {len(failed)} module(s) failed: {', '.join(failed)}",
              file=sys.stderr)
    if failed or not gate_ok:
        sys.exit(1)


if __name__ == "__main__":
    main()
