"""Benchmark-JSON gate checker: one entrypoint for every BENCH artifact.

CI used to carry an inline ``python -c`` snippet per benchmark; those gates
now live here, unchanged, keyed by file basename.  Each checker raises
``AssertionError`` (with the offending payload) on a regression, so the CI
step fails exactly as the inline snippets did.

Run:  python tools/check_bench.py --file bench.json --file bench_serving.json
      python tools/check_bench.py --file BENCH_training.json

Dispatch (substring of the basename, first match wins):
  bench.json / *throughput*  batched-sampling speedup records present
  *serving*                  drain sweep + (when present) load/bucketing gates
  *kernels*                  fused step-kernel record count
  *stability*                EES25 frontier finite and >= reversible-heun
  *rev(ersible)_adaptive*    adjoint zoo presence, grad parity, memory win
  *adaptive*                 adaptive & fixed record groups present
  *resilience*               delegated to benchmarks.bench_resilience.check
  *training*                 scanned-step speedup + DP bitwise parity (PR 10)
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (REPO, os.path.join(REPO, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def check_throughput(d: dict) -> None:
    r = d["records"]
    assert len(r) >= 6, r
    assert all("speedup_bulk" in x for x in r), r


def check_serving(d: dict) -> None:
    r = d["records"]
    assert len(r) >= 6, r
    depths = {x["queue_depth"] for x in r}
    assert len(depths) >= 3 and all("requests_per_sec" in x for x in r), r
    multi = [x for x in r if x["ticks_per_dispatch"] > 1]
    assert multi and all(x["host_dispatches"] < x["n_ticks"] for x in multi), r
    single = [x for x in r if x["ticks_per_dispatch"] == 1]
    assert all(x["host_dispatches"] == x["n_ticks"] for x in single), r
    # bench_load merges its sections into the same JSON; gate them when there.
    if "load" in d:
        load = d["load"]
        for k in ("p50_ms", "p99_ms", "saturation_rps"):
            assert k in load and math.isfinite(load[k]) and load[k] > 0, load
        assert load["p50_ms"] <= load["p99_ms"], load
        assert load["dispatches_per_tick"] <= 1.0, load
        assert d["records"], d  # load section merged, drain sweep intact
    if "bucketing" in d:
        b = d["bucketing"]
        assert b["n_executables_bucketed"] <= b["n_buckets"] < b["n_signatures"], b
        assert b["n_executables_unbucketed"] == b["n_signatures"], b
        assert b["saturation_rps_bucketed"] > 0 and b["saturation_rps_unbucketed"] > 0, b
        if b["cold_compile_s"] is not None:  # None: cache dir fixed by env
            assert b["warm_compile_s"] < b["cold_compile_s"], b


def check_kernels(d: dict) -> None:
    r = d["records"]
    assert len(r) >= 12, r


def check_stability(d: dict) -> None:
    fr = d["frontiers"]
    assert d["records"], d
    for lam in (f"{s:g}" for s in d["stiffness"]):
        ees = fr["ees25"][lam]["max_stable_h"]
        rh = fr["reversible-heun"][lam]["max_stable_h"]
        assert math.isfinite(ees) and ees > 0, (lam, ees)
        assert ees >= rh, (lam, ees, rh)


def check_rev_adaptive(d: dict) -> None:
    r = {x["adjoint"]: x for x in d["records"]}
    assert {"full", "recursive", "reversible", "reversible-bulk"} <= set(r), r
    assert r["reversible"]["grad_rel_err_vs_full"] < 1e-3, r
    assert r["reversible"]["temp_bytes"] < r["full"]["temp_bytes"], r


def check_adaptive(d: dict) -> None:
    r = d["records"]
    assert r["adaptive"] and r["fixed"], r


def check_resilience(d: dict) -> None:
    from benchmarks.bench_resilience import check

    check(d)


def check_training(d: dict) -> None:
    r = d["records"]
    assert r, d
    num_keys = ("us_per_step_sequential", "us_per_step_scanned",
                "steps_per_sec_sequential", "steps_per_sec_scanned",
                "speedup_scan")
    for x in r:
        for k in num_keys:
            assert k in x and math.isfinite(x[k]) and x[k] > 0, (k, x)
    # On CPU the scanned chunk must beat K host-threaded dispatches at the
    # largest K (the tentpole claim); tiny configs can be compute-bound at
    # low K, so the gate is on the best K-max record, not every record.
    k_max = max(x["steps_per_call"] for x in r)
    assert math.isfinite(d["speedup_scan_k8"]), d["speedup_scan_k8"]
    if d.get("device") == "cpu":
        assert d["speedup_scan_k8"] > 1, [
            x for x in r if x["steps_per_call"] == k_max]
    # Sharded DP must match the single-device trajectory bitwise whenever the
    # ladder ran (devices > 1; empty on single-device CI).
    for m in d.get("mesh_records", []):
        assert m["grads_bitwise_vs_single"], m


CHECKS = (
    ("throughput", check_throughput),
    ("serving", check_serving),
    ("kernels", check_kernels),
    ("stability", check_stability),
    ("rev_adaptive", check_rev_adaptive),
    ("reversible_adaptive", check_rev_adaptive),
    ("adaptive", check_adaptive),
    ("resilience", check_resilience),
    ("training", check_training),
)


def checker_for(path: str):
    base = os.path.basename(path).lower()
    if base == "bench.json":
        return check_throughput
    for key, fn in CHECKS:
        if key in base:
            return fn
    raise SystemExit(f"no gate registered for {path!r}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--file", action="append", required=True, dest="files",
                    help="benchmark JSON to gate (repeatable)")
    args = ap.parse_args(argv)
    for path in args.files:
        with open(path) as f:
            data = json.load(f)
        fn = checker_for(path)
        fn(data)
        print(f"OK {path} [{fn.__name__}]")
    return 0


if __name__ == "__main__":
    sys.exit(main())
