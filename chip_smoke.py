#!/usr/bin/env python3
"""Drive the main paths once on a TPU and check what comes out.

One process, float32, random weights from ``--seed``.  With no arguments it
needs one chip and runs, in order:

1. device check — fails unless ``jax.devices()[0].platform == "tpu"``;
2. training — the Neural Langevin SDE of ``examples/train_ou_nsde.py`` at its
   own widths (d_z = 32, width = 32), ``make_sde_train_step("ees25", ...,
   adjoint="reversible")`` at 4096 paths under ``make_scanned_step(step, 8)``,
   two calls = 16 optimizer steps on the OU moment target;
3. sampling — the trained model behind ``AsyncSDESampleEngine``: 8 fixed-grid
   ``ees25`` requests, each checked against an offline ``sdeint`` over the
   same path keys;
4. fused kernels — a diagonal-noise solve whose per-path state (1024 floats)
   is large enough for the Pallas ``sde_step`` kernels, value and parameter
   gradient under the reversible adjoint, ``use_kernels=True`` against the
   plain solver; fails unless the fused program holds a ``tpu_custom_call``.

``--chips 4`` runs only the paths that span chips, each against its
one-device twin: the data-parallel scanned train step (``make_train_mesh``,
``mesh_axis="dp"``) and the sampling engine with its slot axis sharded over a
4-device mesh.

Any failed phase raises, so the exit code is non-zero.  The last line of
standard output is one JSON object: ``{"ok": true, "device": {"platform":
..., "kind": ..., "count": ...}}``.

Run:  python chip_smoke.py [--chips 4] [--seed 0]
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

# Training: the example's widths, with 4096 Monte-Carlo paths per step.
D_Z, WIDTH, N_PATHS, N_STEPS, SAVE_EVERY, T_END = 32, 32, 4096, 32, 8, 2.0
STEPS_PER_CALL, N_CALLS = 8, 2
# Sampling traffic: (n_paths, t1, n_steps, seed); both horizons share h.
REQUESTS = [(64, 1.5, 24, 11), (256, 2.0, 32, 12), (1024, 1.5, 24, 13),
            (64, 2.0, 32, 14), (256, 1.5, 24, 15), (1024, 2.0, 32, 16),
            (256, 2.0, 32, 17), (1024, 1.5, 24, 18)]
SLOTS, TICKS_PER_DISPATCH = 256, 2
# Engine vs offline sdeint, and fused vs plain: max |a - b| / max(1, max |b|).
SAMPLE_TOL, FUSED_TOL, DP_TOL = 1e-4, 1e-4, 1e-4
FUSED_DIM, FUSED_PATHS, FUSED_STEPS = 1024, 64, 16

CUSTOM_CALL = 'custom_call_target="tpu_custom_call"'


def say(phase: str, **kv) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


def rel_diff(a, b) -> float:
    """max |a - b| / max(1, max |b|) over matching pytrees."""
    import jax
    import numpy as np

    num, den = 0.0, 1.0
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
        num = max(num, float(np.max(np.abs(x - y))))
        den = max(den, float(np.max(np.abs(y))))
    return num / den


def bitwise(a, b) -> bool:
    import jax
    import numpy as np

    return all(np.array_equal(np.asarray(x), np.asarray(y)) for x, y in
               zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)))


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise AssertionError(msg)


# -- 1. device ----------------------------------------------------------------

def phase_device(n_chips: int):
    import jax

    devs = jax.devices()
    d = devs[0]
    say("device", platform=d.platform, kind=repr(d.device_kind),
        count=len(devs))
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (jax.devices()[0] is "
                         f"{d.platform!r}); this run has no CPU fallback")
    if len(devs) < n_chips:
        raise SystemExit(f"chip_smoke: --chips {n_chips} needs {n_chips} "
                         f"devices, found {len(devs)}")
    return d, len(devs)


# -- 2. training --------------------------------------------------------------

def build_train(seed: int, *, n_paths: int = N_PATHS, mesh=None):
    """The example's LSDE, its optimizer and its target; returns the scanned
    step plus fresh ``(params, opt_state, counters)``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.nsde import init_lsde, lsde_readout, lsde_term, moment_mse
    from repro.nsde.data import ou_paths
    from repro.optim import adamw, cosine_schedule
    from repro.train.trainer import (init_scan_counters, make_scanned_step,
                                     make_sde_train_step)

    n_saves = N_STEPS // SAVE_EVERY
    rng = np.random.default_rng(seed)
    target = jnp.asarray(ou_paths(rng, 8192, n_saves, T=T_END)[:, 1:],
                         jnp.float32)
    params = init_lsde(jax.random.PRNGKey(seed), d_obs=1, d_z=D_Z, width=WIDTH)
    opt = adamw(cosine_schedule(1e-2, 10, 150))

    def loss_of_result(p, r):
        ys = lsde_readout(p, r.ys)[..., 0]  # (n_paths, n_saves)
        return moment_mse(ys, target)

    step = make_sde_train_step(
        "ees25", lsde_term(), opt,
        y0_fn=lambda p: jnp.zeros(D_Z, jnp.float32) + p["encoder"]["b"],
        loss_fn_result=loss_of_result, t0=0.0, t1=T_END, n_steps=N_STEPS,
        n_paths=n_paths, adjoint="reversible", save_every=SAVE_EVERY,
        mesh=mesh, mesh_axis=None if mesh is None else "dp")
    scanned = make_scanned_step(step, STEPS_PER_CALL)
    return scanned, (params, opt.init(params), init_scan_counters())


def compile_step(scanned, carry, key):
    """AOT-compile the scanned step once; returns (executable, seconds)."""
    import jax.numpy as jnp

    t = time.perf_counter()
    compiled = scanned.lower(*carry, key, jnp.int32(0)).compile()
    return compiled, time.perf_counter() - t


def run_train(step, carry, key, n_calls: int, tag: str):
    """``n_calls`` calls of the compiled scanned step; returns (params,
    losses, per-call seconds)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    params, opt_state, counters = carry
    losses, secs = [], []
    for c in range(n_calls):
        t = time.perf_counter()
        params, opt_state, counters, hist = step(
            params, opt_state, counters, key,
            jnp.asarray(c * STEPS_PER_CALL, jnp.int32))
        jax.block_until_ready((params, hist))
        secs.append(time.perf_counter() - t)
        losses.extend(np.asarray(hist["loss"]).tolist())
        say(tag, call=c + 1, wall_s=f"{secs[-1]:.6f}",
            skipped=int(np.asarray(hist["skipped"]).sum()))
    return params, losses, secs


def phase_train(seed: int):
    import jax
    import numpy as np

    scanned, carry = build_train(seed)
    key = jax.random.PRNGKey(seed + 1)
    step, compile_s = compile_step(scanned, carry, key)
    n_cc = step.as_text().count(CUSTOM_CALL)
    params, losses, secs = run_train(step, carry, key, N_CALLS, "train")
    n = N_CALLS * STEPS_PER_CALL
    say("train", losses="[" + ", ".join(f"{x:.6g}" for x in losses) + "]")
    say("train", n_losses=len(losses), first=f"{losses[0]:.6g}",
        last=f"{losses[-1]:.6g}", compile_s=f"{compile_s:.6f}",
        first_call_s=f"{secs[0]:.6f}",
        warm_call_s=f"{secs[-1]:.6f}",
        warm_s_per_step=f"{secs[-1] / STEPS_PER_CALL:.6f}")
    say("train", tpu_custom_calls_in_scanned_step=n_cc,
        note="count, not a timing: the LSDE state (d_z=32) is under the "
             "kernel tile, so sde_step runs its ref twin")
    check(len(losses) == n, f"expected {n} losses, got {len(losses)}")
    check(bool(np.all(np.isfinite(losses))), f"non-finite loss: {losses}")
    stats = jax.devices()[0].memory_stats() or {}
    say("train", peak_bytes_in_use=stats.get("peak_bytes_in_use",
                                             "not reported"))
    return params


# -- 3. sampling --------------------------------------------------------------

async def serve(params, requests, *, slots: int = SLOTS, mesh=None):
    """Submit every request to one async engine and await every result."""
    import jax.numpy as jnp

    from repro.nsde import lsde_term
    from repro.serving import AsyncSDESampleEngine, SDESampleConfig

    y0 = jnp.zeros(D_Z, jnp.float32) + params["encoder"]["b"]
    cfg = SDESampleConfig(slots=slots, ticks_per_dispatch=TICKS_PER_DISPATCH,
                          mesh=mesh, mesh_axis=None if mesh is None else "mc")
    async with AsyncSDESampleEngine(lsde_term(), y0, cfg, args=params) as eng:
        rids = [await eng.submit("ees25", t1=t1, n_steps=n, n_paths=p, seed=s)
                for p, t1, n, s in requests]
        results = [await eng.result(r, numpy=True) for r in rids]
        stats = {"dispatches": eng.executor.n_dispatches,
                 "ticks": eng.executor.n_ticks,
                 **eng.pending(detail=True)["counters"]}
    return results, stats


def offline(params, requests):
    """``sdeint`` over each request's path keys, one batched call per
    horizon (paths are pure functions of their keys)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import sdeint
    from repro.core.sdeint import path_keys
    from repro.nsde import lsde_term

    y0 = jnp.zeros(D_Z, jnp.float32) + params["encoder"]["b"]
    out = [None] * len(requests)
    for t1, n in sorted({(t1, n) for _, t1, n, _ in requests}):
        idx = [i for i, r in enumerate(requests) if r[1:3] == (t1, n)]
        keys = jnp.concatenate([path_keys(jax.random.PRNGKey(requests[i][3]),
                                          requests[i][0]) for i in idx])
        solve = jax.jit(lambda p, k: sdeint(
            lsde_term(), "ees25", 0.0, t1, n, y0, None, args=p,
            batch_keys=k).y_final)
        ys = np.asarray(solve(params, keys))
        o = 0
        for i in idx:
            out[i] = ys[o:o + requests[i][0]]
            o += requests[i][0]
    return out


def phase_sample(params, requests=REQUESTS, *, slots: int = SLOTS):
    import numpy as np

    t = time.perf_counter()
    results, stats = asyncio.run(serve(params, requests, slots=slots))
    wall = time.perf_counter() - t
    ref = offline(params, requests)
    worst, all_bitwise = 0.0, True
    for (p, t1, n, s), res, want in zip(requests, results, ref):
        check(res.y_final.shape == (p, D_Z), f"shape {res.y_final.shape}")
        check(bool(np.all(np.isfinite(res.y_final))), f"non-finite seed {s}")
        check(res.retries == 0, f"seed {s}: {res.retries} retries")
        n_div = 0 if res.diverged is None else int(np.sum(res.diverged))
        check(n_div == 0, f"seed {s}: {n_div} diverged paths")
        d = rel_diff(res.y_final, want)
        worst = max(worst, d)
        all_bitwise &= bitwise(res.y_final, want)
    say("sample", requests=len(results), wall_s=f"{wall:.6f}",
        max_rel_diff_vs_sdeint=f"{worst:.3e}", tol=SAMPLE_TOL,
        bitwise=all_bitwise, **stats)
    check(worst <= SAMPLE_TOL, f"engine vs offline sdeint {worst:.3e} > "
                               f"{SAMPLE_TOL}")
    return results


# -- 4. fused kernels ---------------------------------------------------------

def phase_fused(seed: int, *, dim: int = FUSED_DIM, n_paths: int = FUSED_PATHS):
    import jax
    import jax.numpy as jnp

    from repro.core import SDETerm, sdeint
    from repro.core.sdeint import path_keys

    term = SDETerm(
        drift=lambda t, y, p: jnp.tanh(p["w"] * y + p["b"]) - p["k"] * y,
        diffusion=lambda t, y, p: p["s"] * (1.0 + 0.1 * jnp.cos(y)),
        noise="diagonal")
    ks = jax.random.split(jax.random.PRNGKey(seed + 2), 5)
    params = {"w": jax.random.normal(ks[0], (dim,)),
              "b": 0.1 * jax.random.normal(ks[1], (dim,)),
              "k": 0.5 + jax.random.uniform(ks[2], (dim,)),
              "s": 0.3 * jax.random.uniform(ks[3], (dim,))}
    y0 = jax.random.normal(ks[4], (dim,))
    keys = path_keys(jax.random.PRNGKey(seed + 3), n_paths)

    def value_and_grad(spec):
        def loss(p):
            r = sdeint(term, spec, 0.0, 1.0, FUSED_STEPS, y0, None, args=p,
                       adjoint="reversible", batch_keys=keys)
            return jnp.mean(r.y_final ** 2)
        return jax.jit(jax.value_and_grad(loss))

    fused = value_and_grad("ees25:use_kernels=True").lower(params).compile()
    plain = value_and_grad("ees25").lower(params).compile()
    n_fused = fused.as_text().count(CUSTOM_CALL)
    n_plain = plain.as_text().count(CUSTOM_CALL)
    (vf, gf), (vp, gp) = fused(params), plain(params)
    dv, dg = rel_diff(vf, vp), rel_diff(gf, gp)
    say("fused", state_per_path=dim, paths=n_paths,
        tpu_custom_calls_fused=n_fused, tpu_custom_calls_plain=n_plain,
        value=f"{float(vf):.6g}", rel_diff_value=f"{dv:.3e}",
        rel_diff_grad=f"{dg:.3e}", tol=FUSED_TOL,
        bitwise=bitwise((vf, gf), (vp, gp)))
    check(bool(jnp.isfinite(vf)), "fused value is not finite")
    check(dv <= FUSED_TOL and dg <= FUSED_TOL,
          f"fused vs plain: value {dv:.3e}, grad {dg:.3e} > {FUSED_TOL}")
    check(n_fused > 0, "the fused program holds no tpu_custom_call: "
                       "the Pallas kernels did not run")


# -- four chips ---------------------------------------------------------------

def phase_four_chips(seed: int, n_dev: int) -> None:
    """DP scanned step and sharded engine, each against one device."""
    import jax

    from repro.launch.mesh import make_sample_mesh, make_train_mesh

    failures = []
    key = jax.random.PRNGKey(seed + 1)
    mesh = make_train_mesh(n_dev)
    # Both steps at full float32 matmul precision.  At the TPU's default the
    # DP step's per-path matvecs (params tiled per path) and the one-device
    # step's shared matmul take different bf16 MXU passes, and 16 steps
    # drift apart by ~3e-3; that measures rounding, not the sharding.
    with jax.default_matmul_precision("highest"):
        dp, carry = build_train(seed, mesh=mesh)
        dp, c_dp = compile_step(dp, carry, key)
        # The DP program must all-gather the per-path results and gradients
        # (train/trainer.py); a program with none put everything on one
        # device.
        n_ag = dp.as_text().count("all-gather")
        p_dp, l_dp, s_dp = run_train(dp, carry, key, N_CALLS, "dp4")
        single, carry1 = build_train(seed)
        single, c_1 = compile_step(single, carry1, key)
        p_1, l_1, s_1 = run_train(single, carry1, key, N_CALLS, "dp1")
    dl, dpar = rel_diff(l_dp, l_1), rel_diff(p_dp, p_1)
    say("dp", devices=n_dev, matmul_precision="highest", all_gathers=n_ag,
        rel_diff_first_loss=f"{rel_diff(l_dp[0], l_1[0]):.3e}",
        rel_diff_losses=f"{dl:.3e}", rel_diff_params=f"{dpar:.3e}", tol=DP_TOL,
        bitwise=bitwise((l_dp, p_dp), (l_1, p_1)),
        compile_s_dp=f"{c_dp:.6f}", compile_s_single=f"{c_1:.6f}",
        warm_call_s_dp=f"{s_dp[-1]:.6f}", warm_call_s_single=f"{s_1[-1]:.6f}")
    if n_ag == 0:
        failures.append("DP step has no all-gather")
    if max(dl, dpar) > DP_TOL:
        failures.append(f"DP vs single: losses {dl:.3e}, params {dpar:.3e}")

    sharded, st_sh = asyncio.run(serve(p_1, REQUESTS,
                                       mesh=make_sample_mesh(n_dev)))
    plain, st_pl = asyncio.run(serve(p_1, REQUESTS))
    worst = max(rel_diff(a.y_final, b.y_final) for a, b in zip(sharded, plain))
    same = all(bitwise(a.y_final, b.y_final) for a, b in zip(sharded, plain))
    say("mesh_engine", devices=n_dev, max_rel_diff=f"{worst:.3e}",
        tol=SAMPLE_TOL, bitwise=same, dispatches_sharded=st_sh["dispatches"],
        dispatches_plain=st_pl["dispatches"],
        retries=st_sh["retries"] + st_pl["retries"])
    if worst > SAMPLE_TOL:
        failures.append(f"sharded vs plain engine {worst:.3e}")
    if failures:
        raise AssertionError("; ".join(failures))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        from repro.compile_cache import enable_compile_cache
    except ImportError as e:
        raise SystemExit(f"chip_smoke: the repro package is not under "
                         f"{os.path.join(HERE, 'src')}: {e}")
    cache = enable_compile_cache()
    say("setup", compile_cache=cache)
    dev, count = phase_device(args.chips)

    t0 = time.perf_counter()
    if args.chips == 4:
        phase_four_chips(args.seed, args.chips)
    else:
        params = phase_train(args.seed)
        phase_sample(params)
        phase_fused(args.seed)
    say("done", wall_s=f"{time.perf_counter() - t0:.3f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
