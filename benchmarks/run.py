"""Benchmark driver — one module per paper figure/table.

Prints ``name,us_per_call,derived`` CSV rows.  A suite that raises is
reported with its traceback, the other suites still run, and the driver
exits 1.

  fig5_engine           real serving engine (CPU, reduced config)
  fig6_routing_overhead optimal vs METRO routing wall-clock
  fig8_activated        max activated experts: EPLB / METRO / optimal
  fig9_10_e2e           simulated TPOT + total throughput
  fig11_breakdown       per-layer latency breakdown
  fig12_pareto          decode Pareto frontier over TPxEPxbatch
  engine_scale          bucketing/paging compile discipline + Poisson load
  pareto_slo            cluster throughput-at-fixed-SLO (METRO vs EPLB)
  prefix_cache          TTFT/pages-saved vs prefix-hit rate (METRO vs EPLB)
  moe_kernels           fused expert-FFN megakernel vs two-pass (HBM
                        bytes model + dead-tile DMA accounting)
  expert_paging         tokens/s vs HBM budget through the paged
                        expert-weight pool (METRO vs EPLB, prefetch
                        on/off)

Regression recording: ``--record`` persists the deterministic
virtual-clock metrics of the suites in ``RECORDED`` to
``BENCH_<suite>.json`` at the repo root; ``--check`` compares a fresh
run against the recorded numbers within ``REL_TOL`` and exits 1 on
drift.  Only fast-mode proxy numbers are recorded (CI runs the check
with ``--fast``); the nightly full sweeps rely on each bench's own
asserts instead.
"""
import argparse
import json
import os
import sys
import time
import traceback

# make `from benchmarks import ...` (and `repro` without an installed
# wheel) work when invoked as a script: python benchmarks/run.py puts
# benchmarks/ itself on sys.path, not the repo root
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

RECORDED = ("expert_paging", "pareto_slo")
REL_TOL = 0.10


def _bench_path(key: str) -> str:
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(root, f"BENCH_{key}.json")


def _record(key: str, rows, fast: bool) -> None:
    payload = {"suite": key, "mode": "fast" if fast else "full",
               "rel_tol": REL_TOL,
               "rows": {name: val for name, val, _ in rows}}
    with open(_bench_path(key), "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"# recorded {len(rows)} rows -> BENCH_{key}.json",
          file=sys.stderr)


def _check(key: str, rows, fast: bool) -> list:
    path = _bench_path(key)
    if not os.path.exists(path):
        return [f"{key}: no recorded baseline ({path})"]
    with open(path) as f:
        ref = json.load(f)
    if ref.get("mode") != ("fast" if fast else "full"):
        return [f"{key}: baseline recorded in {ref.get('mode')} mode, "
                f"run with matching --fast to compare"]
    got = {name: val for name, val, _ in rows}
    errs = []
    for name, want in ref["rows"].items():
        if name not in got:
            errs.append(f"{key}: row {name} missing from this run")
            continue
        tol = REL_TOL * max(abs(want), 1e-9)
        if abs(got[name] - want) > tol:
            errs.append(f"{key}: {name} = {got[name]:.1f}, recorded "
                        f"{want:.1f} (>{REL_TOL:.0%} drift)")
    return errs


def _asserted(rows_checks):
    """Unwrap a (rows, checks) bench result, enforcing every boolean
    self-check (the standalone main()s assert the same flags)."""
    rows, checks = rows_checks
    bad = [k for k, v in checks.items()
           if isinstance(v, bool) and not v]
    assert not bad, f"self-checks failed: {bad}"
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark prefixes to run")
    ap.add_argument("--fast", action="store_true",
                    help="reduced trial counts")
    ap.add_argument("--record", action="store_true",
                    help="persist deterministic metrics of recordable "
                         "suites to BENCH_<suite>.json")
    ap.add_argument("--check", action="store_true",
                    help="fail (exit 1) if a recordable suite drifts "
                         "from its BENCH_<suite>.json baseline")
    args = ap.parse_args()

    from benchmarks import (bench_engine_scale, bench_expert_paging,
                            bench_moe_kernels, bench_pareto_slo,
                            bench_prefix_cache, fig5_engine,
                            fig6_routing_overhead,
                            fig8_activated_experts, fig9_10_e2e,
                            fig11_breakdown, fig12_pareto)
    suites = {
        "engine_scale": lambda: bench_engine_scale.run(fast=args.fast),
        "pareto_slo": lambda: _asserted(
            bench_pareto_slo.run(fast=args.fast)),
        "prefix_cache": lambda: bench_prefix_cache.run(
            fast=args.fast)[0],
        "moe_kernels": lambda: bench_moe_kernels.run(fast=args.fast)[0],
        "expert_paging": lambda: _asserted(
            bench_expert_paging.run(fast=args.fast)),
        "fig6": lambda: fig6_routing_overhead.run(),
        "fig8": lambda: fig8_activated_experts.run(
            trials=3 if args.fast else 8),
        "fig9": lambda: fig9_10_e2e.run(),
        "fig11": lambda: fig11_breakdown.run(),
        "fig12": lambda: fig12_pareto.run(),
        "fig5": lambda: fig5_engine.run(),
    }
    only = args.only.split(",") if args.only else None
    print("name,us_per_call,derived")
    failures = []
    for key, fn in suites.items():
        if only and not any(key.startswith(o) for o in only):
            continue
        t0 = time.time()
        try:
            rows = fn()
        except Exception as e:  # keep the other suites running
            traceback.print_exc()
            print(f"{key}_ERROR,0,{type(e).__name__}:{e}",
                  file=sys.stdout)
            failures.append(f"{key}: raised {type(e).__name__}")
            continue
        for name, us, derived in rows:
            print(f"{name},{us:.1f},{derived}")
        print(f"# {key} done in {time.time()-t0:.1f}s", file=sys.stderr)
        if key in RECORDED:
            if args.record:
                _record(key, rows, args.fast)
            if args.check:
                failures.extend(_check(key, rows, args.fast))
    if failures:
        for f in failures:
            print(f"# FAILED {f}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
