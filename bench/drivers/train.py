"""Training cells: the compiled scanned step, called back to back.

Set-up builds one object -- the scanned step compiled for this cell's path
count and mesh, with its weights from ``--seed`` -- and drives it through
its first ``1 + warm_calls`` calls.  The window keeps calling that same
object, each call ``steps_per_call`` optimizer steps.  As the program's
``train_loop`` does, the host does not wait for a call before it issues the
next: it reads a call's loss history only once later calls are queued
behind it (at least one, and about the mix's ``queue_seconds`` of device
work), so the device always has calls in hand.  The window closes when the
last call issued has finished.

The first call's eight steps are what the check compares with the plain
reference (which follows the same steps from the same seed): every step's
loss, the optimizer's pre-clip norm of the first gradient, and by leaf the
norm of AdamW's first moment and of the parameters' change after the call.
"""
from __future__ import annotations

import collections
import gc
import time

from bench import common, generator
from bench.flops import train_step_flops
from bench.reference.data import make_target


def setup(cell, seed, devices):
    """Build and compile the scanned step, make the weights, and drive the
    first ``1 + warm_calls`` calls.  Returns the state the window goes on
    from and what the check needs of the first call."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.train.trainer import init_scan_counters

    cfg, mix = cell["cfg"], cell["mix"]
    prog = common.family(cfg, "models")
    chips = len(devices)
    K = mix["steps_per_call"]
    n_paths = cfg["train"]["paths_per_chip"] * chips
    target = make_target(cfg["data"])
    wkey = jnp.asarray(generator.key_words(seed, "weights"))
    tkey = jnp.asarray(generator.key_words(seed, "train"))

    mesh = None
    if mix.get("mesh_axis"):
        from repro.launch.mesh import make_train_mesh

        mesh = make_train_mesh(chips, axis=mix["mesh_axis"])
    step, opt = prog.build_train(cfg, target, n_paths=n_paths,
                                 steps_per_call=K, mesh=mesh,
                                 mesh_axis=mix.get("mesh_axis"))
    params = prog.init_params(cfg, wkey)
    opt_state, counters = opt.init(params), init_scan_counters()
    t = time.perf_counter()
    compiled = step.lower(params, opt_state, counters, tkey,
                          np.int32(0)).compile()
    t_compile = time.perf_counter() - t

    p0 = jax.device_get(params)
    params, opt_state, counters, hist = compiled(params, opt_state, counters,
                                                 tkey, np.int32(0))
    first = jax.device_get({"loss": hist["loss"], "gnorm": hist["grad_norm"],
                            "params": params, "mu": opt_state.mu})
    common.log(f"setup_compile_s={t_compile:.3f} "
               f"setup_first_call_s={time.perf_counter() - t - t_compile:.3f}")
    step0 = K
    t = time.perf_counter()
    for _ in range(mix["warm_calls"]):
        params, opt_state, counters, hist = compiled(
            params, opt_state, counters, tkey, np.int32(step0))
        jax.block_until_ready((params, hist))
        step0 += K
    t_call = (time.perf_counter() - t) / mix["warm_calls"]
    return {"compiled": compiled, "carry": (params, opt_state, counters),
            "step0": step0, "first": first, "p0": p0, "target": target,
            "wkey": wkey, "tkey": tkey, "n_paths": n_paths, "t_call": t_call}


def _failed_steps(hist) -> int:
    """Steps of one call the guard skipped or whose loss is not finite;
    reading them waits for that call."""
    import jax
    import numpy as np

    loss, skipped = jax.device_get((hist["loss"], hist["skipped"]))
    return int(np.sum(skipped | ~np.isfinite(loss)))


def run(cell, *, seed, seconds, devices, trace_dir, counter, t_start):
    import jax
    import numpy as np

    cfg, K = cell["cfg"], cell["mix"]["steps_per_call"]
    s = setup(cell, seed, devices)
    compiled, tkey, step0 = s["compiled"], s["tkey"], s["step0"]
    params, opt_state, counters = s.pop("carry")
    chips, n_paths = len(devices), s["n_paths"]

    tracing = trace_dir is not None
    if tracing:
        jax.profiler.start_trace(trace_dir)
    calls = failed = 0
    counter.armed = True
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    # calls kept queued ahead of the one read back: about queue_seconds of
    # device work, so that a host stall shorter than that idles nothing
    depth = max(1, int(cell["mix"].get("queue_seconds", 0) / s["t_call"]))
    common.log(f"calls_in_flight={depth + 1}")
    pending = collections.deque()
    with common.span(tracing, "bench.window"):
        while True:
            with common.span(tracing, "bench.train_call"):
                params, opt_state, counters, hist = compiled(
                    params, opt_state, counters, tkey, np.int32(step0))
            step0 += K
            calls += 1
            pending.append(hist)
            if len(pending) > depth:
                with common.span(tracing, "bench.block"):
                    failed += _failed_steps(pending.popleft())
            if time.perf_counter() - t0 >= seconds:
                break
        with common.span(tracing, "bench.block"):
            jax.block_until_ready((params, opt_state))
            failed += sum(_failed_steps(h) for h in pending)
    t1 = time.perf_counter()
    counter.armed = False
    if tracing:
        jax.profiler.stop_trace()
    mem = common.memory_peak_bytes(devices)
    del params, opt_state, counters, hist, compiled
    s.pop("compiled")
    gc.collect()

    steps_per_s = calls * K / (t1 - t0)
    t_ref = time.perf_counter()
    # the reference at the configuration's precision, also when the program
    # ran at another (the control)
    with jax.default_matmul_precision(cfg["precision"]["matmul"]):
        checks = check(cell, s, cfg["precision"]["matmul"])
    common.log(f"reference_s={time.perf_counter() - t_ref:.3f}")
    return {
        "end_to_end": {"train_steps_per_s": steps_per_s,
                       "train_peak_hbm_mib": mem / 2 ** 20 if mem else None,
                       "setup_s": setup_s},
        "run": {"kind": "train", "steps_per_s": steps_per_s,
                "flops_per_step": train_step_flops(cfg, n_paths),
                "chips": chips},
        "attempted": calls * K, "failed": failed,
        "memory_peak_bytes": mem, "checks": checks,
        "complete": bool(np.all(np.isfinite(s["first"]["loss"]))),
    }


def reference_run(cell, target, wkey, tkey, n_paths, precision):
    """The plain reference over the first call's steps, host arrays out."""
    import jax

    cfg, K = cell["cfg"], cell["mix"]["steps_per_call"]
    ref = common.family(cfg, "reference")
    m = cfg["model"]

    @jax.jit
    def go(wkey, tkey, target):
        p0 = ref.init_params(wkey, m["d_obs"], m["d_z"], m["width"])
        out = ref.train(cfg, p0, target, tkey, 0, K, n_paths, precision)
        return p0, out

    return jax.device_get(go(wkey, tkey, target))


def _change(after, before):
    import jax
    import numpy as np

    return common.leaf_norms(jax.tree_util.tree_map(np.subtract, after,
                                                    before))


def compare(first, p0, ref_p0, ref_out) -> dict:
    """The four numbers the check holds against its limits."""
    import numpy as np

    g1 = common.leaf_norms(ref_out["grad_first"])
    med = sorted(g1.values())[len(g1) // 2]
    # Leaves the reference does not move (under a thousandth of the median
    # leaf's first gradient) are left out: only round-off moves them.
    keep = [k for k, v in g1.items() if v >= 1e-3 * med]
    losses_r = np.asarray(ref_out["losses"], np.float64)
    losses_p = np.asarray(first["loss"], np.float64)
    return {
        "loss_gap": float(np.max(np.abs(losses_p - losses_r)
                                 / np.abs(losses_r))),
        "gnorm_gap": common.rel_gap(float(first["gnorm"][0]),
                                    float(ref_out["gnorms"][0])),
        "mu_gap": common.leaf_norm_gaps(common.leaf_norms(first["mu"]),
                                        common.leaf_norms(ref_out["mu"]),
                                        keep),
        "dparam_gap": common.leaf_norm_gaps(_change(first["params"], p0),
                                            _change(ref_out["params"], ref_p0),
                                            keep),
    }


def check(cell, s, precision):
    """The first call of set-up ``s`` against the reference."""
    ref_p0, ref_out = reference_run(cell, s["target"], s["wkey"], s["tkey"],
                                    s["n_paths"], precision)
    return compare(s["first"], s["p0"], ref_p0, ref_out)
