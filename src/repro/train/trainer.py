"""Training loop: jit'd step, step scanning, grad accumulation, checkpointing.

Three layers, each optional, all composable (PR 10 — the training-side twin
of the serving plane's multi-tick dispatch):

* ``make_sde_train_step`` — ONE optimizer update from one Monte-Carlo batch,
  with in-jit gradient accumulation over ``microbatches`` of the path axis
  (remat'd, so ``n_paths`` beyond memory still trains) and an optional
  mesh-sharded data-parallel variant (``mesh``/``mesh_axis``) that shards the
  path axis over devices with **bitwise-identical** loss and gradients to the
  single-device step (per-path gradients are gathered and reduced in the same
  order a single device reduces them — no ``psum`` reassociation).
* ``make_scanned_step`` — ``steps_per_call=K`` optimizer updates inside one
  jit'd ``lax.scan`` with a donated ``(params, opt_state, counters)`` carry:
  one host round trip per K steps instead of per step.  Metrics histories
  (loss / grad-norm / skipped) accumulate on device and are fetched once per
  chunk.  Scanned chunks are bitwise-equal to sequential steps (tested for
  all three adjoints, fixed and adaptive grids), so ``K`` is a pure
  throughput knob — it never changes the trajectory.
* ``train_loop`` / ``resilient_train_loop`` — host-side driving, chunked
  when ``steps_per_call > 1``: checkpoint cadence moves to chunk boundaries,
  the PR-9 skip guard's rollback/streak logic runs at chunk granularity from
  the per-chunk ``skipped`` history, and metric fetches are batched (no
  per-step blocking ``float(...)`` sync; ``n_dispatches`` in the result is
  the regression-tested dispatch count).
"""
from __future__ import annotations

import dataclasses
import inspect
import time
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import ModelOptions, loss_fn, make_train_step
from repro.optim import adamw, cosine_schedule
from repro.train.checkpoint import latest_step, restore_checkpoint, save_checkpoint
from repro.train.fault_tolerance import recovery_plan

__all__ = ["TrainLoopConfig", "train_loop", "make_accum_train_step",
           "make_sde_train_step", "make_scanned_step", "init_scan_counters",
           "ResilienceConfig", "resilient_train_loop"]


@dataclasses.dataclass(frozen=True)
class TrainLoopConfig:
    steps: int = 100
    ckpt_every: int = 50
    ckpt_dir: Optional[str] = None
    microbatches: int = 1  # gradient-accumulation factor
    log_every: int = 10
    steps_per_call: int = 1  # optimizer steps fused into one jit dispatch


def make_accum_train_step(cfg, optimizer, opts: ModelOptions, microbatches: int = 1,
                          accum_dtype=None, grad_constraint=None):
    """train_step with in-jit gradient accumulation over ``microbatches``.

    ``accum_dtype``: dtype of the gradient-accumulation buffer (default f32;
    bf16 halves the buffer for >16B-param models at ~8-bit mantissa cost over
    <=32 microbatches — noted in EXPERIMENTS.md §Perf).

    ``grad_constraint``: optional fn applied to the accumulation carry each
    microbatch.  Passing a data-axis sharding constraint turns the
    per-microbatch gradient all-reduce into a reduce-scatter onto a sharded
    buffer (ZeRO-2): 1/dp the buffer memory and ~half the bytes on the wire;
    the optimizer then updates shard-locally and params all-gather once."""
    if microbatches <= 1:
        return make_train_step(cfg, optimizer, opts)
    import jax.numpy as _jnp

    adt = accum_dtype or _jnp.float32
    constrain = grad_constraint or (lambda t: t)

    def step(params, opt_state, batch):
        # batch leaves: (microbatches, local_batch/mb, ...)
        def acc(carry, mb):
            gsum, lsum = carry
            loss, g = jax.value_and_grad(lambda p: loss_fn(cfg, p, mb, opts))(params)
            gsum = jax.tree_util.tree_map(
                lambda a, b: a + b.astype(adt), gsum, g
            )
            gsum = constrain(gsum)
            return (gsum, lsum + loss), None

        zeros = constrain(
            jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape, adt), params)
        )
        (gsum, lsum), _ = jax.lax.scan(acc, (zeros, jnp.zeros(())), batch)
        grads = jax.tree_util.tree_map(lambda g: g / microbatches, gsum)
        params, opt_state, gnorm = optimizer.update(grads, opt_state, params)
        return params, opt_state, {"loss": lsum / microbatches, "grad_norm": gnorm}

    return step


def make_sde_train_step(
    solver,
    term,
    optimizer,
    y0_fn: Callable,
    loss_fn_result: Callable,
    *,
    t0: float,
    t1: float,
    n_steps: int,
    n_paths: int,
    adjoint: str = "reversible",
    save_every: Optional[int] = None,
    save_at=None,
    rtol: Optional[float] = None,
    atol: Optional[float] = None,
    remat_chunk: Optional[int] = None,
    bulk_increments: bool = True,
    noise_shape=None,
    guard: bool = True,
    microbatches: int = 1,
    mesh=None,
    mesh_axis: Optional[str] = None,
):
    """Neural-SDE analogue of ``make_train_step``: one Monte-Carlo batch of
    ``n_paths`` trajectories through ``sdeint``, a loss on the result, one
    optimizer update.

    ``solver`` is a registry spec string (``"ees25"``, ``"mcf-rk4"``,
    ``"ees25:adaptive"``, ...) or a solver object; ``y0_fn(params)`` produces
    the (shared) initial state; ``loss_fn_result(params, result)`` maps the
    batched result (leading axis ``n_paths``) to a scalar.  The returned step
    is ``(params, opt_state, key) -> (params, opt_state, metrics)`` and is
    jit- and scan-compatible (``key`` may be a traced value — see
    :func:`make_scanned_step`); path ``i`` derives its key as
    ``path_keys(key, n_paths)[i]``, matching the serving engine's convention.

    Adaptive solves (an ``:adaptive`` spec) take ``rtol``/``atol`` and a
    ``save_at`` output grid, with ``n_steps`` as the trial-step budget.  Every
    adjoint works on them — each path realizes its accepted-step grid
    (gradient-stopped controller) and the backward pass runs over that
    realized grid, so the default O(1)-memory ``"reversible"`` adjoint now
    trains on adaptive grids too (tolerance-driven step placement *and*
    constant trajectory memory in one step function).

    ``bulk_increments`` (default ``True``) is the PR-4 throughput
    configuration: all Brownian increments realized in one batched pass and
    streamed through the solve — see ``docs/performance.md``.  Set it
    ``False`` for the strict memory-lean configuration (per-step noise
    recompute, no O(n_steps x noise) buffer in the backward residuals).

    ``guard`` (default ``True``) is the trainer half of the PR-9 divergence
    guard (``docs/robustness.md``): when the loss or any gradient leaf comes
    back non-finite, the optimizer update is **skipped** — params and
    opt_state pass through unchanged — and ``metrics["skipped"]`` is 1.
    One blown Monte-Carlo batch then costs one wasted step instead of
    poisoning the parameters (every later step would be NaN).  The guard is
    in-jit (one fused ``where``-select traversal over the joined
    ``(params, opt_state)`` tree, no host sync) and bitwise-inert on finite
    steps: ``where(True, new, old)`` is ``new``.  Pair it with
    :func:`resilient_train_loop` for checkpoint rollback when skips persist.

    ``microbatches`` > 1 accumulates gradients over that many equal slices of
    the path axis inside the jit (a remat'd ``lax.scan`` over per-slice
    ``value_and_grad``), trading compute scheduling for peak memory so
    ``n_paths`` beyond a device's capacity still trains.  The reported loss
    and gradient are the *mean over slices* — identical to the full batch in
    exact arithmetic for path-decomposable (mean-type) losses; cross-path
    moment losses see per-slice estimates (document the loss you train).

    ``mesh``/``mesh_axis`` shard the Monte-Carlo path axis over a device mesh
    (:func:`repro.launch.mesh.make_sample_mesh` /
    :func:`~repro.launch.mesh.make_train_mesh`) with ``shard_map``.  Loss and
    gradients are **bitwise-identical** to a single-device step that solves
    its batch path by path: parameters are tiled per path, the sharded
    ``vjp`` yields *per-path* gradients (no in-``shard_map`` cross-path
    reduction, hence no ``psum`` reassociation), which are gathered to
    replicated and summed in the same order the single-device vmap transpose
    sums them.  A single-device reversible batch large enough to be solved
    as one (:func:`~repro.core.sdeint.sdeint`) sums over paths at every
    reverse step instead, and agrees to rounding.  Cross-path losses are
    supported — the loss runs on the gathered (replicated) result.

    The step's parts run under five ``jax.named_scope``s — ``sde_brownian``,
    ``sde_forward``, ``sde_reverse``, ``sde_loss``, ``sde_optimizer`` — that
    name the compiled instructions' ``op_name`` for a profiler and change
    nothing else (``docs/performance.md``).
    """
    from repro.core import get_solver, sdeint
    from repro.core.pytree import tree_blowup
    from repro.core.sdeint import path_keys

    solver = get_solver(solver)
    extra = {}
    if rtol is not None:
        extra["rtol"] = rtol
    if atol is not None:
        extra["atol"] = atol
    if save_at is not None:
        extra["save_at"] = jnp.asarray(save_at)
    if remat_chunk is not None:
        extra["remat_chunk"] = remat_chunk
    extra["bulk_increments"] = bulk_increments

    microbatches = max(int(microbatches), 1)
    if n_paths % microbatches != 0:
        raise ValueError(
            f"microbatches={microbatches} does not divide n_paths={n_paths}"
        )
    chunk_paths = n_paths // microbatches

    if mesh_axis is not None:
        if mesh is None:
            raise ValueError(
                "mesh_axis given without mesh: pass mesh="
                "make_sample_mesh()/make_train_mesh() explicitly"
            )
        n_dev = mesh.shape[mesh_axis]
        if chunk_paths % n_dev != 0:
            raise ValueError(
                f"mesh axis {mesh_axis!r} of size {n_dev} does not divide "
                f"the per-microbatch path count {chunk_paths}"
            )
    elif mesh is not None:
        raise ValueError("mesh given without mesh_axis; name the axis to shard over")

    def batch_loss(p, keys):
        r = sdeint(
            term, solver, t0, t1, n_steps, y0_fn(p), None, args=p,
            adjoint=adjoint, save_every=save_every,
            noise_shape=noise_shape, batch_keys=keys, **extra,
        )
        with jax.named_scope("sde_loss"):
            return loss_fn_result(p, r)

    if mesh_axis is None:
        lg_fn = batch_loss if microbatches == 1 else jax.checkpoint(batch_loss)

        def value_and_grad_batch(params, keys):
            return jax.value_and_grad(lg_fn)(params, keys)
    else:
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P
        rep = NamedSharding(mesh, P())

        def one_path(p, k):
            return sdeint(
                term, solver, t0, t1, n_steps, y0_fn(p), k, args=p,
                adjoint=adjoint, save_every=save_every,
                noise_shape=noise_shape, **extra,
            )

        solve_tiled = jax.shard_map(
            lambda pt, ks: jax.vmap(one_path)(pt, ks),
            mesh=mesh, in_specs=(P(mesh_axis), P(mesh_axis)),
            out_specs=P(mesh_axis), check_vma=False,
        )

        def value_and_grad_batch(params, keys):
            nb = jax.tree_util.tree_leaves(keys)[0].shape[0]
            p_t = jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x, (nb,) + jnp.shape(x)), params
            )
            # vjp wrt the *tiled* params: the pullback returns per-path
            # gradients — the cross-path sum happens below, replicated, in
            # vmap-transpose order, which is what makes the sharded step
            # bitwise-equal to the single-device one.  Integer result leaves
            # (adaptive controller counts) ride along as vjp aux.
            cell = {}

            def fwd(pt):
                r = solve_tiled(pt, keys)
                leaves, treedef = jax.tree_util.tree_flatten(r)
                is_f = [jnp.issubdtype(l.dtype, jnp.inexact) for l in leaves]
                cell["treedef"], cell["is_f"] = treedef, is_f
                floats = [l for l, f in zip(leaves, is_f) if f]
                aux = [l for l, f in zip(leaves, is_f) if not f]
                return floats, aux

            floats, pull, aux = jax.vjp(fwd, p_t, has_aux=True)
            gather = lambda xs: [  # noqa: E731
                jax.lax.with_sharding_constraint(x, rep) for x in xs
            ]
            floats, aux = gather(floats), gather(aux)
            treedef, is_f = cell["treedef"], cell["is_f"]

            def merged_loss(pp, fls):
                fit, ait = iter(fls), iter(aux)
                leaves = [next(fit) if f else next(ait) for f in is_f]
                with jax.named_scope("sde_loss"):
                    return loss_fn_result(
                        pp, jax.tree_util.tree_unflatten(treedef, leaves))

            l, (g_direct, f_bar) = jax.value_and_grad(
                merged_loss, argnums=(0, 1))(params, floats)
            (g_t,) = pull(f_bar)
            g_t = jax.tree_util.tree_map(
                lambda x: jax.lax.with_sharding_constraint(x, rep), g_t
            )
            g_paths = jax.tree_util.tree_map(lambda x: jnp.sum(x, 0), g_t)
            g = jax.tree_util.tree_map(lambda a, b: a + b, g_direct, g_paths)
            return l, g

    def step(params, opt_state, key):
        with jax.named_scope("sde_brownian"):
            keys = path_keys(key, n_paths)
        if microbatches == 1:
            l, g = value_and_grad_batch(params, keys)
        else:
            kchunks = keys.reshape((microbatches, chunk_paths) + keys.shape[1:])

            def acc(gsum, kc):
                l, g = value_and_grad_batch(params, kc)
                gsum = jax.tree_util.tree_map(lambda a, b: a + b, gsum, g)
                return gsum, l

            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(jnp.shape(p), jnp.result_type(p)), params
            )
            gsum, ls = jax.lax.scan(acc, zeros, kchunks)
            l = jnp.mean(ls)
            g = jax.tree_util.tree_map(lambda x: x / microbatches, gsum)

        with jax.named_scope("sde_optimizer"):
            if not guard:
                params, opt_state, gnorm = optimizer.update(g, opt_state,
                                                            params)
                return params, opt_state, {"loss": l, "grad_norm": gnorm}
            bad = tree_blowup(g) | ~jnp.isfinite(l)
            new_p, new_s, gnorm = optimizer.update(g, opt_state, params)
            keep = lambda new, old: jnp.where(bad, old, new)  # noqa: E731
            params, opt_state = jax.tree_util.tree_map(
                keep, (new_p, new_s), (params, opt_state)
            )
        return params, opt_state, {"loss": l, "grad_norm": gnorm,
                                   "skipped": bad}

    return step


def init_scan_counters():
    """Device-resident counters threaded through a scanned step's carry:
    ``steps`` dispatched and guard-``skipped`` totals (int32 scalars)."""
    return {"steps": jnp.zeros((), jnp.int32),
            "skipped": jnp.zeros((), jnp.int32)}


def make_scanned_step(step_fn: Callable, steps_per_call: int, *,
                      jit: bool = True, donate: bool = True) -> Callable:
    """Fuse ``steps_per_call`` optimizer updates into ONE jit dispatch.

    ``step_fn`` is a *traceable* ``(params, opt_state, key) ->
    (params, opt_state, metrics)`` step (a :func:`make_sde_train_step`
    product; a fn taking an extra trailing ``step`` argument —
    ``(params, opt_state, key, step)`` — is also accepted, which is how
    tests inject step-indexed faults in-graph).  The returned callable is

        ``scanned(params, opt_state, counters, key, step0)
            -> (params, opt_state, counters, metrics_hist)``

    running global steps ``step0 .. step0 + K - 1`` inside one ``lax.scan``
    with a donated ``(params, opt_state, counters)`` carry; step ``s`` uses
    ``fold_in(key, s)``, exactly the sequential loops' convention, so the
    result is **bitwise-identical** to K un-scanned steps (tested across all
    three adjoints on fixed and adaptive grids).  Each leaf of ``metrics``
    comes back as a ``(K,)`` history — fetch it once per chunk, not per step.
    ``counters`` (:func:`init_scan_counters`) accumulate dispatched/skipped
    step totals on device.  ``step0`` may vary per call without retracing
    (pass it as an int array); chunks of different length need different
    scanned fns (the loops keep a per-length cache).
    """
    K = int(steps_per_call)
    if K < 1:
        raise ValueError(f"steps_per_call must be >= 1, got {steps_per_call}")
    try:
        takes_step = len(inspect.signature(step_fn).parameters) >= 4
    except (TypeError, ValueError):  # jitted/wrapped fn with opaque signature
        takes_step = False

    def scanned(params, opt_state, counters, key, step0):
        def body(carry, s):
            p, o, c = carry
            with jax.named_scope("sde_brownian"):
                k = jax.random.fold_in(key, s)
            p, o, m = (step_fn(p, o, k, s) if takes_step
                       else step_fn(p, o, k))
            sk = m.get("skipped", False) if isinstance(m, dict) else False
            with jax.named_scope("sde_optimizer"):
                c = {"steps": c["steps"] + 1,
                     "skipped": c["skipped"]
                     + jnp.asarray(sk).astype(jnp.int32)}
            return (p, o, c), m

        (params, opt_state, counters), hist = jax.lax.scan(
            body, (params, opt_state, counters),
            step0 + jnp.arange(K, dtype=jnp.asarray(step0).dtype))
        return params, opt_state, counters, hist

    if jit:
        scanned = jax.jit(scanned, donate_argnums=(0, 1, 2) if donate else ())
    return scanned


def train_loop(
    cfg,
    params,
    data_iter,
    *,
    optimizer=None,
    opts: ModelOptions = ModelOptions(),
    loop: TrainLoopConfig = TrainLoopConfig(),
    step_fn: Optional[Callable] = None,
    to_device: Callable = lambda b: b,
) -> Dict[str, Any]:
    """Drive a batch-consuming step.  With ``loop.steps_per_call = K > 1``
    the loop stacks K batches and runs them through one jit'd ``lax.scan``
    per dispatch (``step_fn`` must then be traceable); metric fetches are
    batched into ONE device→host transfer at the end either way, and the
    result carries ``n_dispatches`` — the number of jit calls issued — for
    the dispatch-count regression test."""
    optimizer = optimizer or adamw(cosine_schedule(3e-4, 10, loop.steps))
    opt_state = optimizer.init(params)
    start = 0
    if loop.ckpt_dir:
        last = latest_step(loop.ckpt_dir)
        if last is not None:
            params, opt_state = restore_checkpoint(
                loop.ckpt_dir, last, (params, opt_state)
            )
            start = last
    raw_step = step_fn or make_accum_train_step(cfg, optimizer, opts, loop.microbatches)
    K = max(int(loop.steps_per_call), 1)

    t0 = time.time()
    n_dispatches = 0
    pending = []  # (first_logged_step_info, device arrays) — fetched once at end
    # Step-pure sources (batch_at) give exact replay after restart; plain
    # iterators are only correct for fresh runs.
    step_pure = hasattr(data_iter, "batch_at")
    it = None if step_pure else iter(data_iter)
    get_batch = (lambda s: data_iter.batch_at(s)) if step_pure else (lambda s: next(it))

    if K == 1:
        jstep = jax.jit(raw_step, donate_argnums=(0, 1))
        for step in range(start, loop.steps):
            batch = to_device(get_batch(step))
            params, opt_state, metrics = jstep(params, opt_state, batch)
            n_dispatches += 1
            if (step + 1) % loop.log_every == 0 or step == loop.steps - 1:
                pending.append(((step + 1,), metrics["loss"]))
            if loop.ckpt_dir and (step + 1) % loop.ckpt_every == 0:
                save_checkpoint(loop.ckpt_dir, step + 1, (params, opt_state),
                                extra={"steps_per_call": K})
    else:
        chunk_cache: Dict[int, Callable] = {}

        def chunk_fn(length):
            if length not in chunk_cache:
                def scanned(p, o, batches):
                    def body(c, b):
                        pp, oo = c
                        pp, oo, m = raw_step(pp, oo, b)
                        return (pp, oo), m

                    (p, o), hist = jax.lax.scan(body, (p, o), batches)
                    return p, o, hist

                chunk_cache[length] = jax.jit(scanned, donate_argnums=(0, 1))
            return chunk_cache[length]

        step = start
        last_ckpt = start
        while step < loop.steps:
            length = min(K, loop.steps - step)
            batches = [get_batch(s) for s in range(step, step + length)]
            stacked = to_device(jax.tree_util.tree_map(
                lambda *xs: np.stack([np.asarray(x) for x in xs]), *batches))
            params, opt_state, hist = chunk_fn(length)(params, opt_state, stacked)
            n_dispatches += 1
            pending.append(((step, length), hist["loss"]))
            step += length
            if loop.ckpt_dir and step - last_ckpt >= loop.ckpt_every:
                save_checkpoint(loop.ckpt_dir, step, (params, opt_state),
                                extra={"steps_per_call": K})
                last_ckpt = step

    fetched = jax.device_get([d for _, d in pending])  # the ONE metrics sync
    losses = []
    for (info, _), vals in zip(pending, fetched):
        if K == 1:
            losses.append((info[0], float(vals)))
        else:
            s0, length = info
            for j in range(length):
                s1 = s0 + j + 1
                if s1 % loop.log_every == 0 or s1 == loop.steps:
                    losses.append((s1, float(vals[j])))
    return {
        "params": params,
        "opt_state": opt_state,
        "losses": losses,
        "wall_s": time.time() - t0,
        "n_dispatches": n_dispatches,
    }


@dataclasses.dataclass(frozen=True)
class ResilienceConfig:
    """Knobs for :func:`resilient_train_loop` (see ``docs/robustness.md``).

    ``skip_patience`` consecutive guard-skipped steps trigger a rollback to
    the latest checkpoint (the blow-up evidently was not a one-off batch);
    checkpoints are written every ``ckpt_every`` *productive* boundaries so a
    rollback never restores a state reached through skipped steps.
    ``mesh_shape`` / ``hosts_per_pod`` feed :func:`recovery_plan` when the
    heartbeat monitor reports dead hosts.

    ``steps_per_call = K > 1`` runs the loop in chunked mode: K steps per
    jit dispatch via :func:`make_scanned_step` (``step_fn`` must be
    traceable), ONE metrics fetch per chunk, and the skip/rollback policy
    evaluated from the chunk's ``skipped`` history at chunk granularity —
    a rollback triggered at in-chunk position ``j`` restores the latest
    checkpoint and re-dispatches the remaining steps from it.  On a
    fault-free run the trajectory is bitwise-identical to stepwise mode;
    after a rollback it can differ, because checkpoints land on chunk
    boundaries (the first boundary with ``ckpt_every`` productive steps
    since the last save), so the restored state may be older than the one
    per-step cadence would have kept.  Same policy, chunk-granular
    cadence — the price of never syncing more than once per K steps."""

    steps: int = 100
    ckpt_every: int = 10
    ckpt_dir: Optional[str] = None
    skip_patience: int = 3
    mesh_shape: tuple = (1, 1, 1)
    hosts_per_pod: int = 1
    steps_per_call: int = 1


def resilient_train_loop(
    step_fn: Callable,
    params,
    opt_state,
    key,
    *,
    res: ResilienceConfig = ResilienceConfig(),
    monitor=None,
    tracker=None,
    host: int = 0,
) -> Dict[str, Any]:
    """Drive a guarded SDE train step with skip-streak rollback and fleet
    health bookkeeping — the trainer-side divergence story (PR 9 + PR 10).

    ``step_fn`` is a (possibly jit'd) ``make_sde_train_step`` product:
    ``(params, opt_state, key) -> (params, opt_state, metrics)``.  Step
    ``i`` uses ``fold_in(key, i)``, so the trajectory is reproducible and a
    rollback replays the identical keys it first saw.

    With ``res.steps_per_call = 1`` (default) the loop dispatches per step
    and may use any Python-level ``step_fn`` (fault-injection dispatchers
    included); losses are kept on device and fetched in ONE transfer at the
    end.  With ``K > 1`` it dispatches :func:`make_scanned_step` chunks —
    ``step_fn`` must be traceable — and fetches each chunk's metric
    histories once; the skip streak carries across chunk boundaries and the
    rollback policy replays from the rollback point (see
    :class:`ResilienceConfig`).

    Per dispatch, the loop records step time into ``tracker``
    (:class:`~repro.train.fault_tolerance.StragglerTracker` — per-step in
    stepwise mode, amortized via ``record_chunk`` in chunked mode) and beats
    ``monitor`` (:class:`~repro.train.fault_tolerance.HeartbeatMonitor`);
    when the monitor reports dead hosts, a
    :func:`~repro.train.fault_tolerance.recovery_plan` is computed against
    ``res.mesh_shape`` and appended to the history (the launcher acts on
    it; this in-process loop keeps training its own shard).

    The guard's ``metrics["skipped"]`` drives the rollback policy: after
    ``res.skip_patience`` consecutive skips the loop restores the latest
    checkpoint under ``res.ckpt_dir`` (written every ``res.ckpt_every``
    productive steps — chunk-boundary-aligned when chunked — plus one at
    step 0 so rollback is always possible) and continues.  Returns
    params/opt_state plus a history dict — per-step ``losses`` and
    ``skipped`` flags, ``rollbacks``, ``recovery_plans``, and ``goodput``
    (productive steps / total steps: the resilience metric
    ``benchmarks/bench_resilience.py`` sweeps against fault rate)."""
    K = max(int(res.steps_per_call), 1)
    history: Dict[str, Any] = {"losses": [], "skipped": [], "rollbacks": 0,
                               "recovery_plans": []}
    if res.ckpt_dir:
        save_checkpoint(res.ckpt_dir, 0, (params, opt_state),
                        extra={"steps_per_call": K})
    streak = 0
    productive = 0

    def fleet_beat(dt, n_steps_done):
        if tracker is not None:
            if n_steps_done == 1:
                tracker.record(host, dt)
            else:
                tracker.record_chunk(host, dt, n_steps_done)
        if monitor is not None:
            monitor.beat(host)
            dead = monitor.dead_hosts()
            if dead:
                history["recovery_plans"].append(recovery_plan(
                    res.mesh_shape, res.hosts_per_pod, dead,
                    (latest_step(res.ckpt_dir) or 0) if res.ckpt_dir else 0))

    try:
        takes_step = len(inspect.signature(step_fn).parameters) >= 4
    except (TypeError, ValueError):
        takes_step = False

    if K == 1:
        dev_losses = []
        for step in range(res.steps):
            k = jax.random.fold_in(key, step)
            t_step = time.monotonic()
            params, opt_state, metrics = (
                step_fn(params, opt_state, k, jnp.asarray(step)) if takes_step
                else step_fn(params, opt_state, k))
            skipped = bool(np.asarray(metrics.get("skipped", False)))
            fleet_beat(time.monotonic() - t_step, 1)
            dev_losses.append(metrics["loss"])
            history["skipped"].append(skipped)
            if skipped:
                streak += 1
                if streak >= res.skip_patience and res.ckpt_dir:
                    last = latest_step(res.ckpt_dir)
                    if last is not None:
                        params, opt_state = restore_checkpoint(
                            res.ckpt_dir, last, (params, opt_state))
                        history["rollbacks"] += 1
                        streak = 0
            else:
                streak = 0
                productive += 1
                if res.ckpt_dir and (step + 1) % res.ckpt_every == 0:
                    save_checkpoint(res.ckpt_dir, step + 1,
                                    (params, opt_state),
                                    extra={"steps_per_call": K})
        history["losses"] = [float(x) for x in jax.device_get(dev_losses)]
    else:
        scan_cache: Dict[int, Callable] = {}

        def scanned_for(length):
            if length not in scan_cache:
                scan_cache[length] = make_scanned_step(step_fn, length)
            return scan_cache[length]

        counters = init_scan_counters()
        step = 0
        since_ckpt = 0
        while step < res.steps:
            length = min(K, res.steps - step)
            t_chunk = time.monotonic()
            p2, o2, counters, hist = scanned_for(length)(
                params, opt_state, counters, key, jnp.asarray(step))
            # the chunk's ONE device->host sync: loss + skipped histories
            m = jax.device_get({
                "loss": hist["loss"],
                "skipped": hist.get("skipped", np.zeros(length, bool)),
            })
            fleet_beat(time.monotonic() - t_chunk, length)
            sk = np.asarray(m["skipped"]).astype(bool)
            commit = length
            rolled = False
            for j in range(length):
                history["losses"].append(float(m["loss"][j]))
                history["skipped"].append(bool(sk[j]))
                if sk[j]:
                    streak += 1
                    if streak >= res.skip_patience and res.ckpt_dir:
                        last = latest_step(res.ckpt_dir)
                        if last is not None:
                            params, opt_state = restore_checkpoint(
                                res.ckpt_dir, last, (params, opt_state))
                            history["rollbacks"] += 1
                            streak = 0
                            since_ckpt = 0
                            commit = j + 1
                            rolled = True
                            break
                else:
                    streak = 0
                    productive += 1
                    since_ckpt += 1
            if not rolled:
                params, opt_state = p2, o2
            step += commit
            if res.ckpt_dir and not rolled and since_ckpt >= res.ckpt_every:
                save_checkpoint(res.ckpt_dir, step, (params, opt_state),
                                extra={"steps_per_call": K})
                since_ckpt = 0
    history["goodput"] = productive / max(res.steps, 1)
    return {"params": params, "opt_state": opt_state, **history}
