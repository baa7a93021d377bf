#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is the ``workloads`` entry ``<name>`` of ``BENCHMARK.json``; its
configuration, traffic mix, limits and per-layer metrics are files found by
the names there (``bench/configs``, ``bench/traffic``, ``bench/limits``,
``bench/metrics``), and the traffic mix's ``kind`` names the driver
(``bench/drivers/<kind>.py``) that builds, warms, times and checks it.

Set-up runs from process start to the first timed call.  Then the window
runs for ``--seconds`` with nothing compiled inside it.  ``--trace 0``
reports the cell's end-to-end metrics; ``--trace 1`` records the window with
the JAX profiler and reports the per-layer metrics, ``busy_s``/``window_s``
and a ``breakdown``.  Either way, once the window has closed and the
program's state is freed, what the timed path produced is compared with the
plain reference: the last lines on standard error and the ``checks`` key of
the result give each compared number beside its limit.

The last line of standard output is one JSON object.  Without a TPU, or with
fewer chips than the cell asks for, the run exits non-zero and prints none.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import common  # noqa: E402

TRACE_DIR = os.path.join(_ROOT, "bench_out", "trace")


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             devices=None, overrides=None, keep_trace: str = None,
             precision: str = None) -> dict:
    """One run of cell ``name``; returns the result object.

    ``devices`` skips the look for a chip (tests pass CPU devices);
    ``overrides`` replaces entries of the configuration and mix (tests
    shrink the cell to what a CPU holds); ``precision`` runs the program
    at another matrix product precision than the configuration states (the
    control), while the reference keeps the configuration's."""
    cell = common.load_cell(name)
    for part, values in (overrides or {}).items():
        for k, v in values.items():
            cell[part][k] = v
    import jax

    common.log(f"setup_import_s={time.perf_counter() - T_START:.3f}")
    if devices is None:
        devices = common.check_devices(cell["chips"])
    common.log(f"setup_devices_s={time.perf_counter() - T_START:.3f}")
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    driver = __import__(f"bench.drivers.{cell['mix']['kind']}",
                        fromlist=["run"])
    trace_dir = None
    if trace:
        trace_dir = keep_trace or os.path.join(TRACE_DIR, f"{name}-{seed}")
        shutil.rmtree(trace_dir, ignore_errors=True)
        # a mix whose window makes a trace too large to read traces less
        seconds = min(seconds, cell["mix"].get("trace_seconds", seconds))
    counter = common.CompileCounter()
    # set process-wide, not as a (thread-local) context, so that whatever
    # thread a later driver compiles from sees it
    old = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision",
                      precision or cell["cfg"]["precision"]["matmul"])
    try:
        out = driver.run(cell, seed=seed, seconds=seconds, devices=devices,
                         trace_dir=trace_dir, counter=counter,
                         t_start=T_START)
    finally:
        jax.config.update("jax_default_matmul_precision", old)
    common.log(f"compilations_in_window={counter.count}")

    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": jax.device_count(),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    metrics = {}
    breakdown = None
    if trace:
        from bench.trace_reduce import reduce_trace

        red = reduce_trace(trace_dir)
        if keep_trace is None:
            shutil.rmtree(trace_dir, ignore_errors=True)
        out["run"]["trace"] = red
        out["run"]["peaks"] = common.peaks(d0.device_kind)
        if red is not None:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            breakdown = {"device_ops": red["device_ops"],
                         "idle_gaps": red["idle_gaps"]}
        for m in cell["per_layer"]:
            v = common.metric_reader(m["name"])(out["run"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in cell["end_to_end"]:
            metrics[m["name"]] = {"value": out["end_to_end"][m["name"]],
                                  "unit": m["unit"]}
    checks, ok = common.checks_block(out["checks"], cell["limits"])
    for k, v in checks.items():
        common.log(f"check {k}={v['value']!r} limit={v['limit']!r}")
    result = {"correct": bool(ok and out["complete"]),
              "attempted": out["attempted"], "failed": out["failed"],
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="keep the profiler trace in this directory")
    args = ap.parse_args(argv)
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), keep_trace=args.keep_trace)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
