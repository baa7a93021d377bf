#!/usr/bin/env python3
"""Readings that set the limits of a cell's check, made on the chip.

    python3 bench/control.py --workload <name> --seeds 101-112 \
        --control-seeds 101-103 [--seconds 1]

For each of ``--seeds`` it reads the numbers a run compares from the
program itself at the cell's own size: the set-up (compile, first call) of
a run against the reference.  The largest over the seeds is the lower
reading.

For each of ``--control-seeds`` it makes a whole run of the cell (a window
of ``--seconds``) with the program at the matrix product precision one step
below the configuration's -- ``"high"``, three bfloat16 passes, for float32
at ``"highest"`` -- and the reference at the configuration's: the control,
which has to come out not correct.  With the same seeds it reads each fault
the cell can have, planted in the reference put in the program's place:
half of the batch left out (the mean over the other half) and, on a mesh,
the exchange between chips left out (one chip's share of the paths).  A
step that returns its state unchanged reads 1 in ``mu_gap`` and
``dparam_gap`` by their definition and needs no run.

One JSON object per reading goes to standard output; the last line sums
them up.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import common  # noqa: E402

# the nearest matrix product precision below each a configuration can state
LOWER = {"highest": "high"}


def _seeds(text: str):
    if "-" in text:
        a, b = text.split("-")
        return list(range(int(a), int(b) + 1))
    return [int(x) for x in text.split(",")]


def _emit(rows, **row):
    rows.append(row)
    print(json.dumps(row), flush=True)


def _as_program(ref_out):
    return {"loss": ref_out["losses"], "gnorm": ref_out["gnorms"],
            "params": ref_out["params"], "mu": ref_out["mu"]}


def train_readings(name, cell, seeds, control_seeds, devices, rows, *,
                   seconds=1.0, overrides=None, stand_in=False):
    """The program's readings on ``seeds``, and the control's and the
    faults' on ``control_seeds``.  ``stand_in`` puts the reference at the
    lower precision in the program's place instead of running the program
    at it: on the CPU, which computes float32 products in full whatever
    precision is asked, the program's own lower path reads as the program
    does."""
    import gc

    import jax

    from bench.drivers import train as T
    from bench.run import run_cell

    top = cell["cfg"]["precision"]["matmul"]
    low = LOWER[top]
    for seed in seeds:
        with jax.default_matmul_precision(top):
            s = T.setup(cell, seed, devices)
            s.pop("compiled"), s.pop("carry")
            gc.collect()
            args = (cell, s["target"], s["wkey"], s["tkey"])
            n = s["n_paths"]
            ref_p0, want = T.reference_run(*args, n, top)
            _emit(rows, seed=seed, kind="program",
                  **T.compare(s["first"], s["p0"], ref_p0, want))
            if seed not in control_seeds:
                continue
            faults = [("half_batch", n // 2, top)]
            if stand_in:
                faults.append(("control", n, low))
            if cell["mix"].get("mesh_axis"):
                faults.append(("no_exchange", n // len(devices), top))
            for kind, paths, precision in faults:
                _, got = T.reference_run(*args, paths, precision)
                _emit(rows, seed=seed, kind=kind,
                      **T.compare(_as_program(got), ref_p0, ref_p0, want))
        if stand_in:
            continue
        res = run_cell(name, seed, seconds, False, devices=devices,
                       overrides=overrides, precision=low)
        _emit(rows, seed=seed, kind="control", precision=low,
              correct=res["correct"],
              **{k: v["value"] for k, v in res["checks"].items()})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", required=True)
    ap.add_argument("--seconds", type=float, default=1.0,
                    help="window of the control's runs")
    args = ap.parse_args(argv)
    cell = common.load_cell(args.workload)
    devices = common.check_devices(cell["chips"])
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    seeds, control = _seeds(args.seeds), set(_seeds(args.control_seeds))
    rows = []
    train_readings(args.workload, cell, seeds, control, devices, rows,
                   seconds=args.seconds)
    summary = {}
    for r in rows:
        for k, v in r.items():
            if k.endswith("_gap"):
                key = f"{r['kind']}.{k}"
                agg = max if r["kind"] == "program" else min
                summary[key] = agg(summary.get(key, v), v)
    summary["control.correct"] = [r["correct"] for r in rows
                                  if r["kind"] == "control"]
    print(json.dumps({"workload": args.workload, "summary": summary}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
