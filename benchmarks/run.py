"""Benchmark suite entry point — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (see common.emit):
  table1_ou        — Table 1: high-vol OU stability under training
  table2_vol       — Table 2/H.2: runtime at fixed NFE (2N recurrence win)
  table3_kuramoto  — Table 3 + Fig 5b: T*T^N energy score + adjoint memory
  table4_sphere    — Table 4 + Fig 6: sphere latent SDE + adjoint memory
  table7_gbm       — Table 7/H.1: stiff-GBM stability separation
  fig_convergence  — Figs 7/8 + App. G: strong/backward rates on fBm RDEs
  bench_throughput — beyond-paper: batched sdeint trajectories/sec vs batch

Every module runs even when an earlier one fails; the process then exits
non-zero, naming the modules that failed.
"""
import sys
import time
import traceback


def main() -> None:
    from . import (
        bench_throughput,
        fig_convergence,
        table1_ou,
        table2_vol,
        table3_kuramoto,
        table4_sphere,
        table7_gbm,
    )

    t00 = time.time()
    failed = []
    for mod in (table7_gbm, table1_ou, table2_vol, table3_kuramoto,
                table4_sphere, fig_convergence, bench_throughput):
        name = mod.__name__.split(".")[-1]
        print(f"# --- {name} ---", flush=True)
        t0 = time.time()
        try:
            mod.run()
        except Exception:  # noqa: BLE001 — keep the suite going
            print(f"{name},nan,ERROR")
            traceback.print_exc()
            failed.append(name)
        print(f"# {name} took {time.time()-t0:.1f}s", flush=True)
    print(f"# total {time.time()-t00:.1f}s")
    if failed:
        sys.exit(f"benchmarks failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
