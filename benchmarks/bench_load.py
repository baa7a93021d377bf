"""Async serving-plane load test: latency percentiles under Poisson arrivals.

Where :mod:`benchmarks.bench_serving` measures the *drain* cost of a
pre-filled queue (host dispatches per tick), this benchmark measures what a
client of the **async plane** actually sees: per-request latency when
requests arrive as a seeded Poisson process over a *mixed* population of
solver/horizon/tolerance signatures, and the closed-loop saturation
throughput of the engine.  Two phases, one warm-up:

* **warm** — every signature in the mix is served once so XLA compiles are
  out of the measured path (same discipline as ``bench_serving``);
* **open loop** — ``--requests`` arrivals with exponential inter-arrival
  times at ``--rate`` req/s (``random.Random(seed)``: reproducible arrival
  pattern AND signature mix); each client awaits ``submit`` → ``result``
  and records wall latency.  Reported as ``p50_ms`` / ``p99_ms``;
* **closed loop** — the same request mix submitted all at once and drained:
  completed requests / second is the ``saturation_rps`` ceiling.

Results merge into the ``"load"`` section of ``BENCH_serving.json`` next to
the drain sweep's ``"records"`` — including ``dispatches_per_tick`` over the
measured phases, the PR-5 regression guard (continuous batching must not
cost extra host round trips per device tick).

A second sweep (the ``"bucketing"`` section, PR 8) serves a **mixed-horizon**
population — two solvers x six horizons sharing one step size, most of them
off the power-of-two ladder — from cold, with signature coalescing on and
off, and records what bucketing buys: ``n_executables`` (compile-cache
entries after the drain), cold-start saturation rps for both modes, and the
cold-vs-warm compile seconds of an AOT ``warmup()`` against a persistent
compilation cache directory (``null`` when ``JAX_COMPILATION_CACHE_DIR``
fixes the cache, since a cold compile needs an empty one).  The CI
bench-smoke gate asserts ``n_executables_bucketed <= n_buckets <
n_signatures`` and, when measured, ``warm_compile_s < cold_compile_s`` on
this section.

``--profile DIR`` wraps the measured phases in ``jax.profiler.trace(DIR)``
(inspect with TensorBoard or Perfetto).

Run:  PYTHONPATH=src python -m benchmarks.bench_load [--out PATH]
      [--requests N] [--rate RPS] [--slots N] [--ticks-per-dispatch N]
      [--seed S] [--profile DIR] [--skip-bucketing]
"""
from __future__ import annotations

import argparse
import asyncio
import contextlib
import json
import os
import random
import tempfile
import time

import jax
import jax.numpy as jnp

from repro.serving import AsyncSDESampleEngine, SDESampleConfig

from .bench_serving import DEFAULT_OUT, ou_term
from .common import emit

SLOTS = 32
TICKS_PER_DISPATCH = 4
N_REQUESTS = 40
RATE = 50.0
SEED = 0

# Mixed signature population: solver x horizon x tolerance.  Weights bias
# toward the cheap fixed-grid solve the way a real mix would.
POPULATION = (
    {"name": "ees25/short", "weight": 4, "solver": "ees25",
     "kw": dict(t1=1.0, n_steps=32)},
    {"name": "ees25/long", "weight": 2, "solver": "ees25",
     "kw": dict(t1=2.0, n_steps=64)},
    {"name": "heun/short", "weight": 2, "solver": "heun",
     "kw": dict(t1=1.0, n_steps=32)},
    {"name": "ees25/adaptive", "weight": 1, "solver": "ees25:adaptive",
     "kw": dict(t1=1.0, n_steps=128, rtol=1e-3)},
)


# Mixed-horizon population for the bucketing sweep: every signature shares
# the step size h = 1/32 (the coalescing condition) but takes a different
# number of steps, most off the power-of-two ladder.  12 signatures land on
# 4 buckets (per solver: 24,32 -> rung 32; 40,48,56,64 -> rung 64).
BUCKET_SOLVERS = ("ees25", "heun")
BUCKET_HORIZON_STEPS = (24, 32, 40, 48, 56, 64)


def _bucket_specs():
    return [{"solver": s, "t1": n / 32.0, "n_steps": n}
            for s in BUCKET_SOLVERS for n in BUCKET_HORIZON_STEPS]


def _profile_ctx(profile_dir):
    if profile_dir:
        return jax.profiler.trace(profile_dir)
    return contextlib.nullcontext()


def _percentile(sorted_xs, q: float) -> float:
    if not sorted_xs:
        return float("nan")
    k = min(len(sorted_xs) - 1, max(0, round(q * (len(sorted_xs) - 1))))
    return sorted_xs[k]


def _draw_mix(rng: random.Random, n: int):
    choices = [s for s in POPULATION for _ in range(s["weight"])]
    return [rng.choice(choices) for _ in range(n)]


def _make_engine(slots: int, tpd: int):
    args = {"nu": jnp.float32(0.2), "mu": jnp.float32(0.1),
            "sigma": jnp.float32(2.0)}
    cfg = SDESampleConfig(slots=slots, ticks_per_dispatch=tpd,
                          max_queue_paths=64 * slots)
    return AsyncSDESampleEngine(ou_term(), jnp.ones(16, jnp.float32), cfg,
                                args=args)


async def _warm(eng, slots: int):
    for spec in POPULATION:
        rid = await eng.submit(spec["solver"], n_paths=slots, seed=0,
                               **spec["kw"])
        await eng.result(rid)


async def _open_loop(eng, mix, rng: random.Random, rate: float, slots: int):
    latencies = []

    async def client(k, spec):
        t0 = time.perf_counter()
        rid = await eng.submit(spec["solver"], n_paths=slots, seed=k,
                               **spec["kw"])
        await eng.result(rid)
        latencies.append(time.perf_counter() - t0)

    tasks = []
    for k, spec in enumerate(mix):
        await asyncio.sleep(rng.expovariate(rate))
        tasks.append(asyncio.create_task(client(k, spec)))
    await asyncio.gather(*tasks)
    return sorted(latencies)


async def _closed_loop(eng, mix, slots: int) -> float:
    t0 = time.perf_counter()
    rids = [await eng.submit(spec["solver"], n_paths=slots, seed=k,
                             **spec["kw"])
            for k, spec in enumerate(mix)]
    for rid in rids:
        await eng.result(rid)
    return len(mix) / (time.perf_counter() - t0)


async def _run(slots: int, tpd: int, n_requests: int, rate: float,
               seed: int):
    rng = random.Random(seed)
    mix = _draw_mix(rng, n_requests)
    async with _make_engine(slots, tpd) as eng:
        await _warm(eng, slots)
        d0, t0 = eng.executor.n_dispatches, eng.executor.n_ticks
        lat = await _open_loop(eng, mix, rng, rate, slots)
        sat = await _closed_loop(eng, mix, slots)
        d1, t1 = eng.executor.n_dispatches, eng.executor.n_ticks
    return {
        "slots": slots,
        "ticks_per_dispatch": tpd,
        "n_requests": n_requests,
        "offered_rps": rate,
        "seed": seed,
        "mix": sorted({s["name"] for s in mix}),
        "p50_ms": _percentile(lat, 0.50) * 1e3,
        "p99_ms": _percentile(lat, 0.99) * 1e3,
        "saturation_rps": sat,
        # PR-5 regression guard: host round trips per device tick across the
        # measured phases (1/tpd in steady state; tails/interleave add a bit)
        "dispatches_per_tick": (d1 - d0) / max(1, t1 - t0),
    }


async def _bucket_drain(bucketing: bool, slots: int):
    """Cold drain of the mixed-horizon population; returns (rps, n_exec,
    n_buckets_observed).  Cold on purpose: the executable count — what
    coalescing changes — dominates a fresh engine's drain on every backend."""
    specs = _bucket_specs()
    args = {"nu": jnp.float32(0.2), "mu": jnp.float32(0.1),
            "sigma": jnp.float32(2.0)}
    cfg = SDESampleConfig(slots=slots, ticks_per_dispatch=1,
                          bucketing=bucketing, max_queue_paths=64 * slots)
    t0 = time.perf_counter()
    async with AsyncSDESampleEngine(ou_term(), jnp.ones(16, jnp.float32),
                                    cfg, args=args) as eng:
        rids = [await eng.submit(s["solver"], t1=s["t1"],
                                 n_steps=s["n_steps"], n_paths=slots, seed=k)
                for k, s in enumerate(specs)]
        results = [await eng.result(rid) for rid in rids]
        secs = time.perf_counter() - t0
        n_exec = len(eng.executor._compiled)
    buckets = {r.bucket for r in results if r.bucket is not None}
    return len(specs) / secs, n_exec, len(buckets)


def _bucket_compile_times(slots: int):
    """Cold vs warm AOT ``warmup()`` seconds against a persistent compile
    cache: the warm engine is a fresh process stand-in (its executor cache
    is empty), so its compiles deserialize from disk instead of re-running
    XLA.  A cold compile needs an empty cache directory, so when
    ``JAX_COMPILATION_CACHE_DIR`` fixes the cache the pair is not measured
    (``(None, None)``) rather than repointing the cache elsewhere."""
    from repro.compile_cache import ENV_VAR
    from repro.serving import SDESampleEngine

    if os.environ.get(ENV_VAR):
        return None, None

    specs = [dict(s) for s in _bucket_specs()]
    args = {"nu": jnp.float32(0.2), "mu": jnp.float32(0.1),
            "sigma": jnp.float32(2.0)}
    with tempfile.TemporaryDirectory() as cache_dir:
        cfg = SDESampleConfig(slots=slots, ticks_per_dispatch=1,
                              compile_cache_dir=cache_dir)

        def timed_warmup():
            eng = SDESampleEngine(ou_term(), jnp.ones(16, jnp.float32), cfg,
                                  args=args)
            t0 = time.perf_counter()
            eng.warmup(specs)
            return time.perf_counter() - t0

        cold_s = timed_warmup()
        warm_s = timed_warmup()
    return cold_s, warm_s


def run_bucketing(out_path: str = DEFAULT_OUT, *, slots: int = SLOTS,
                  profile_dir=None):
    """The PR-8 coalescing sweep; merges the ``"bucketing"`` section."""
    n_signatures = len(_bucket_specs())
    with _profile_ctx(profile_dir):
        rps_on, exec_on, n_buckets = asyncio.run(_bucket_drain(True, slots))
        rps_off, exec_off, _ = asyncio.run(_bucket_drain(False, slots))
    # Compile-cache timing LAST: enabling the persistent cache flips global
    # jax config, which must not touch the drains above.
    cold_s, warm_s = _bucket_compile_times(slots)
    section = {
        "slots": slots,
        "n_signatures": n_signatures,
        "n_buckets": n_buckets,
        "n_executables_bucketed": exec_on,
        "n_executables_unbucketed": exec_off,
        "saturation_rps_bucketed": rps_on,
        "saturation_rps_unbucketed": rps_off,
        "speedup_bucketed": rps_on / rps_off,
        "cold_compile_s": cold_s,
        "warm_compile_s": warm_s,
    }
    emit(f"bench_load/bucketing/S{slots}", (1.0 / rps_on) * 1e6,
         f"exec {exec_on}/{exec_off} rps {rps_on:.1f}/{rps_off:.1f} "
         f"speedup={section['speedup_bucketed']:.2f} "
         + (f"compile cold={cold_s:.2f}s warm={warm_s:.2f}s"
            if cold_s is not None else "compile cold/warm not measured"))
    data = {"device": jax.devices()[0].platform, "records": []}
    if os.path.exists(out_path):
        with open(out_path) as f:
            data = json.load(f)
    data["bucketing"] = section
    with open(out_path, "w") as f:
        json.dump(data, f, indent=2)
    print(f"# wrote {out_path}")
    return section


def run(out_path: str = DEFAULT_OUT, *, slots: int = SLOTS,
        tpd: int = TICKS_PER_DISPATCH, n_requests: int = N_REQUESTS,
        rate: float = RATE, seed: int = SEED, profile_dir=None):
    with _profile_ctx(profile_dir):
        load = asyncio.run(_run(slots, tpd, n_requests, rate, seed))
    emit(f"bench_load/R{n_requests}/S{slots}/T{tpd}",
         load["p50_ms"] * 1e3,
         f"p99_ms={load['p99_ms']:.1f} sat_rps={load['saturation_rps']:.1f} "
         f"dpt={load['dispatches_per_tick']:.3f}")
    data = {"device": jax.devices()[0].platform, "records": []}
    if os.path.exists(out_path):
        with open(out_path) as f:
            data = json.load(f)
    data["load"] = load
    with open(out_path, "w") as f:
        json.dump(data, f, indent=2)
    print(f"# wrote {out_path}")
    return load


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=DEFAULT_OUT)
    ap.add_argument("--slots", type=int, default=SLOTS)
    ap.add_argument("--ticks-per-dispatch", type=int,
                    default=TICKS_PER_DISPATCH)
    ap.add_argument("--requests", type=int, default=N_REQUESTS)
    ap.add_argument("--rate", type=float, default=RATE)
    ap.add_argument("--seed", type=int, default=SEED)
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="wrap measured phases in jax.profiler.trace(DIR)")
    ap.add_argument("--skip-bucketing", action="store_true",
                    help="skip the mixed-horizon coalescing sweep")
    args = ap.parse_args()
    run(args.out, slots=args.slots, tpd=args.ticks_per_dispatch,
        n_requests=args.requests, rate=args.rate, seed=args.seed,
        profile_dir=args.profile)
    if not args.skip_bucketing:
        run_bucketing(args.out, slots=args.slots, profile_dir=args.profile)


if __name__ == "__main__":
    main()
