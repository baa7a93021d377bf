"""Batched Monte-Carlo SDE integration: one call, many trajectories, any device.

``sdeint`` is the single entry point above the solver layer.  It owns the
plumbing every caller used to hand-roll — Brownian-driver construction, solver
resolution by registry name, ``jax.vmap`` fan-out over per-trajectory PRNG
keys, and (optionally) ``shard_map`` fan-out over a device-mesh axis — while
delegating the actual integration to ONE generalized
:func:`repro.core.adjoint.solve`.  A fixed grid solves directly; an adaptive
request (``adaptive=True`` or an ``"ees25:adaptive"``-style spec) first
*realizes* its accepted-step grid with the PI controller on a
:class:`~repro.core.brownian.VirtualBrownianTree`
(:func:`repro.core.adaptive.realize_grid`), then runs the same ``solve`` over
the realized grid — so every adjoint, including the O(1)-memory
``"reversible"`` one, works on adaptive grids.

Batching is *by key*: each trajectory draws its own counter-based Brownian
driver from its own key, so the batched result is bitwise identical to a
Python loop of single-trajectory calls over the same keys (tested, for both
the fixed-grid and the adaptive path).  That property is what lets serving
slice a request's paths across engine ticks, or a benchmark compare batch
sizes, without changing a single sample.

``sdeint_ticks`` lifts the same batch one level further: a ``(T, B, ...)``
stack of per-tick key batches runs through a single on-device ``lax.map``
loop over ticks — one host dispatch for ``T`` ticks — with tick ``t``
bitwise equal to ``sdeint(..., batch_keys=tick_keys[t])``.  This is the
serving executor's multi-tick entry (see ``repro.serving.executor``).
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from .adaptive import integrate_adaptive
from .adjoint import SolveResult, solve, solve_reversible_paths
from .brownian import brownian_path, padded_brownian_path, virtual_brownian_tree
from .grid import TimeGrid
from .registry import get_solver

__all__ = ["sdeint", "sdeint_ticks", "path_keys"]

# A batch under the reversible adjoint is solved either path by path (a vmap
# outside the custom vjp) or as one batch (the vmap inside it).  Path by
# path, the backward carries one running sum of the args' cotangent per path
# and streams it from memory at every reverse step: about twice its bytes a
# step.  As one batch, each reverse step's vjp sums over paths itself in a
# few small matrix products, each at a fixed per-op latency.  On a TPU v5e,
# LSDE training with a 72 MB per-path carry (4096 paths, 4,385 parameters)
# ran 10.5 times faster as one batch, and with a 3 MB carry (1024 paths, 745
# parameters, 168 steps) 2% slower.  Batches whose per-path carry exceeds
# this many bytes are solved as one batch.
_PER_PATH_CARRY_BYTES = 16 * 2**20


def path_keys(key: jax.Array, n_paths: int) -> jax.Array:
    """Per-path key batch by ``fold_in`` — THE path-batching convention.

    Path ``i`` of a Monte-Carlo batch always derives its key as
    ``fold_in(key, i)``; the serving engine, the trainer, and offline replay
    all share this function, so a request seed reproduces the same
    trajectories everywhere.  ``key`` may be a *traced* value (a scan carry,
    a per-step ``fold_in(base, step)`` inside a jit'd multi-step training
    chunk): ``fold_in`` is pure integer hashing, so the vmapped derivation
    works identically under ``jit``/``lax.scan`` as it does eagerly.
    """
    return jax.vmap(lambda i: jax.random.fold_in(key, i))(jnp.arange(n_paths))


def _infer_noise_shape(term, y0):
    """Default Brownian-increment shape from the term's noise structure."""
    noise = getattr(term, "noise", "diagonal")
    if noise == "none":
        return ()  # increments are drawn but never consumed
    if noise == "general":
        raise ValueError(
            "noise='general' needs an explicit noise_shape=(..., m) — the "
            "number of driving channels is not derivable from the state"
        )
    if noise == "scalar":
        return ()  # ONE shared channel: the increment is a scalar
    # diagonal/additive: dW matches the state pytree leaf-for-leaf (for a
    # bare-array state this unflattens straight back to its shape tuple)
    leaves, treedef = jax.tree_util.tree_flatten(y0)
    return jax.tree_util.tree_unflatten(treedef, [tuple(l.shape) for l in leaves])


def _infer_dtype(y0):
    for leaf in jax.tree_util.tree_leaves(y0):
        if hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype, jnp.inexact):
            return leaf.dtype
    return jnp.float32


def _ambient_mesh():
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        raise ValueError(
            "mesh_axis given but no mesh: pass mesh=... or call inside "
            "`with jax.set_mesh(mesh):` (see "
            "repro.launch.mesh.make_production_mesh)"
        )
    return mesh


def sdeint(
    term,
    solver,
    t0: float,
    t1: float,
    n_steps: int,
    y0,
    key: Optional[jax.Array] = None,
    *,
    args: Any = None,
    adjoint: str = "full",
    save_every: Optional[int] = None,
    remat_chunk: Optional[int] = None,
    adaptive: bool = False,
    save_at=None,
    rtol: Optional[float] = None,
    atol: Optional[float] = None,
    h0: Optional[float] = None,
    bm_tol: Optional[float] = None,
    bounded: bool = True,
    bulk_increments: bool = True,
    guard: Optional[float] = None,
    noise_shape=None,
    dtype=None,
    batch_keys: Optional[jax.Array] = None,
    mesh=None,
    mesh_axis: Optional[str] = None,
):
    """Integrate ``term`` over ``[t0, t1]``, fixed-grid or adaptively.

    Parameters
    ----------
    term:
        An :class:`~repro.core.solvers.SDETerm` (drift, diffusion, declared
        noise structure).
    solver:
        A registry spec string (``"ees25"``, ``"ees25:x=0.3"``,
        ``"ees25:adaptive"``, ``"reversible_heun"``, ``"mcf-rk4"``, ...) or a
        solver object.  The ``adaptive`` spec flag is equivalent to passing
        ``adaptive=True``.
    t0, t1:
        Integration window.
    n_steps:
        Fixed grid: the number of uniform steps.  Adaptive: the *trial-step
        budget* (accepted + rejected; also the static length of the realized
        grid, whose unused tail is zero-length padding) — if the controller
        exhausts it the result stops short of ``t1`` (check
        ``result.t_final``).
    y0:
        Initial state (pytree).  With ``batch_keys`` it is *shared* across
        trajectories; batch it yourself with an outer ``vmap`` if each
        trajectory starts differently.
    key:
        PRNG key for a single trajectory.  Ignored when ``batch_keys`` is
        given.
    args:
        Passed through to the drift/diffusion callables (typically the
        parameter pytree being trained).
    adjoint:
        ``"full"`` | ``"recursive"`` | ``"reversible"`` — see
        :func:`repro.core.adjoint.solve`.  All three work on both fixed and
        adaptive grids: an adaptive solve realizes its accepted-step grid
        first (gradient-stopped controller), then the chosen adjoint runs
        over the realized grid — the reversible backward sweep replays the
        same non-uniform step sequence, so step rejection never needs a
        third register.  A fixed-grid ``"reversible"`` batch whose per-path
        cotangent carry exceeds ``_PER_PATH_CARRY_BYTES`` is solved as one
        batch (:func:`repro.core.adjoint.solve_reversible_paths`): the same
        samples, gradients equal to rounding (``docs/adjoints.md``).  The
        one unsupported combination is adaptive
        stepping with a solver that has no embedded error estimate
        (``reversible_heun`` / ``mcf-*`` / single-stage schemes) — grid
        *realization* needs ``step_with_error``; realize with an EES scheme
        via :func:`repro.core.adaptive.realize_grid` and solve with any
        solver if you need that pairing.
    save_every:
        Fixed grid only: save ``extract(state)`` every that many steps (must
        divide ``n_steps``); saved states land in ``result.ys``.
    remat_chunk:
        ``adjoint="recursive"``: checkpoint granularity (steps per
        rematerialised chunk, on either grid kind).
    adaptive:
        Integrate with PI-controlled accept/reject steps on a
        :class:`~repro.core.brownian.VirtualBrownianTree` instead of a fixed
        grid.  Returns an :class:`~repro.core.adaptive.AdaptiveResult`
        (``y_final`` / ``ys`` plus controller statistics).
    save_at:
        Adaptive only: 1-D array of output times in ``[t0, t1]``; the
        solution is interpolated between accepted steps onto this grid and
        returned as ``result.ys`` with a leading ``len(save_at)`` axis.
    rtol, atol, h0:
        Adaptive only: tolerances (defaults 1e-4 / 1e-6) and initial step for
        the controller (see
        :func:`repro.core.adaptive.integrate_adaptive`).  Setting any of
        them without ``adaptive`` raises — a tolerance request must not
        silently run a fixed grid.
    bm_tol:
        Adaptive only: leaf resolution of the Virtual Brownian Tree (default
        ``(t1 - t0) / 4096``).
    bounded:
        Adaptive only.  ``True`` (default): realize-then-solve — the grid
        realization runs forward-only, then the solve sweep carries the
        gradients, so every adjoint works.  ``False``: a single forward-only
        controller pass with no second sweep — the fastest way to *sample*
        (the serving engine uses this), not reverse-differentiable.  Results
        are bitwise identical between the two modes.
    bulk_increments:
        ``True`` (default): every step's Brownian increment is generated in
        one batched driver pass (stacked threefry on a fixed grid; one
        batched level-sweep over the Virtual Brownian Tree on a realized
        grid) and streamed through the solve's forward and
        reversible-backward sweeps — bit-identical increments (results and
        gradients match the per-step path to ulp-level), per-step RNG
        hoisted out of the sequential hot loop (see
        ``docs/performance.md``).  ``False`` restores per-step generation.
    guard:
        Blow-up guard threshold (see :func:`repro.core.adjoint.solve`): when
        set, the result carries a per-trajectory ``diverged`` bool (any
        non-finite state entry, or ``|y| > guard``, at any step) computed
        in-loop on device — no host sync, and bitwise-identical solutions
        with the guard on or off.  ``None`` (default) disables it.
    noise_shape:
        Shape of one Brownian increment.  Defaults to the state's shape for
        diagonal noise; required for ``noise="general"``.
    dtype:
        Brownian-increment dtype (defaults to the state's).
    batch_keys:
        ``(B, ...)`` stack of per-trajectory keys.  The result gains a
        leading ``B`` axis on every leaf and is bitwise equal to looping
        single-trajectory calls over the keys.
    mesh, mesh_axis:
        Shard the batch over ``mesh_axis`` of ``mesh`` with ``shard_map``
        (multi-device Monte Carlo).  ``mesh`` defaults to the ambient
        ``with jax.set_mesh(mesh):`` context; the axis size must divide
        ``B``.  Requires ``batch_keys``.

    Returns
    -------
    :class:`~repro.core.adjoint.SolveResult` ``(y_final, ys)`` on a fixed
    grid; :class:`~repro.core.adaptive.AdaptiveResult` (same two fields plus
    ``t_final`` / ``h_final`` / ``n_accepted`` / ``n_rejected``) when
    adaptive.

    Example
    -------
    >>> keys = jax.random.split(jax.random.PRNGKey(0), 1024)
    >>> r = sdeint(term, "ees25", 0.0, 2.0, 64, y0, None, args=params,
    ...            adjoint="reversible", batch_keys=keys)   # (1024, ...) outputs
    >>> ts = jnp.linspace(0.0, 2.0, 33)
    >>> a = sdeint(term, "ees25:adaptive", 0.0, 2.0, 256, y0, None,
    ...            args=params, rtol=1e-3, save_at=ts, batch_keys=keys)
    >>> a.ys  # (1024, 33, ...) dense output on the save_at grid
    """
    one = _trajectory_fn(
        term, solver, t0, t1, n_steps, y0, args=args, adjoint=adjoint,
        save_every=save_every, remat_chunk=remat_chunk, adaptive=adaptive,
        save_at=save_at, rtol=rtol, atol=atol, h0=h0, bm_tol=bm_tol,
        bounded=bounded, bulk_increments=bulk_increments, guard=guard,
        noise_shape=noise_shape, dtype=dtype,
    )

    if batch_keys is None:
        if mesh_axis is not None or mesh is not None:
            raise ValueError("mesh fan-out requires batch_keys")
        if key is None:
            raise ValueError("pass key= for a single trajectory or batch_keys= for a batch")
        return one(key)

    n_batch = jax.tree_util.tree_leaves(batch_keys)[0].shape[0]
    solver = get_solver(solver)
    if (adjoint == "reversible" and bulk_increments and mesh is None
            and mesh_axis is None
            and not (adaptive or getattr(solver, "adaptive", False))
            and _per_path_carry_bytes(args, n_batch) > _PER_PATH_CARRY_BYTES):
        return _reversible_paths_fn(
            term, solver, t0, t1, n_steps, y0, args=args,
            save_every=save_every, guard=guard, noise_shape=noise_shape,
            dtype=dtype)(batch_keys)
    batched = _batched_fn(jax.vmap(one), n_batch, mesh, mesh_axis)
    return batched(batch_keys)


def _per_path_carry_bytes(args, n_paths: int) -> int:
    """Bytes of the args' cotangents kept once per path."""
    return n_paths * sum(
        int(jnp.size(l)) * jnp.dtype(jnp.result_type(l)).itemsize
        for l in jax.tree_util.tree_leaves(args)
        if jnp.issubdtype(jnp.result_type(l), jnp.inexact))


def _reversible_paths_fn(term, solver, t0, t1, n_steps, y0, *, args,
                         save_every, guard, noise_shape, dtype):
    """``keys -> result`` of a fixed-grid reversible batch solved as one
    (:func:`~repro.core.adjoint.solve_reversible_paths`); bitwise the same
    results as the vmapped single-trajectory fn."""
    solver, _, noise_shape, dtype = _resolve(term, solver, y0, "reversible",
                                             noise_shape, dtype)
    needs_levy = getattr(solver, "needs_levy_area", False)

    def increments(k):
        grid = TimeGrid.from_path(
            brownian_path(k, t0, t1, n_steps, shape=noise_shape, dtype=dtype))
        return grid.levy_increments() if needs_levy else grid.increments()

    def batch(keys):
        with jax.named_scope("sde_brownian"):
            dWs = jax.vmap(increments)(keys)
        return solve_reversible_paths(
            solver, term, y0, TimeGrid.uniform(t0, t1, n_steps), args, dWs,
            save_every=save_every, guard=guard)

    return batch


def sdeint_ticks(
    term,
    solver,
    t0: float,
    t1: float,
    n_steps: int,
    y0,
    tick_keys: jax.Array,
    *,
    mesh=None,
    mesh_axis: Optional[str] = None,
    active_steps: Optional[jax.Array] = None,
    step_size: Optional[float] = None,
    **kwargs,
):
    """Integrate a *stack* of key batches in one on-device multi-tick loop.

    ``tick_keys`` is a ``(T, B, ...)`` stack of ``T`` per-tick key batches;
    each tick is exactly one :func:`sdeint` batch of ``B`` trajectories, and
    the ticks run inside a single ``lax.map`` loop — so a caller (the serving
    executor) pays ONE host dispatch for ``T`` ticks instead of one per tick.
    Every result leaf gains a leading ``(T, B)`` pair of axes, and tick ``t``
    is bitwise equal to ``sdeint(..., batch_keys=tick_keys[t])``: trajectories
    are pure functions of their keys, so looping on-device instead of from the
    host leaves no trace in the samples (regression-tested).

    ``mesh``/``mesh_axis`` shard each tick's **batch** axis over the device
    mesh exactly as in :func:`sdeint` (the tick axis stays sequential — ticks
    are the serving time dimension, not a parallel one).  All other keyword
    arguments are as for :func:`sdeint`.

    **Padded bucketed mode** (``active_steps`` + ``step_size``, PR 8): the
    stack becomes a *bucket* executable — ``n_steps`` is the padded grid
    length (the bucket's ladder rung), ``step_size`` the exact static step
    ``h`` every tick shares, and ``active_steps`` a ``(T,)`` int32 operand
    giving each tick's true (live) step count.  Tick ``t`` is then bitwise
    equal to ``sdeint(term, solver, t0, t0 + active_steps[t]*h,
    active_steps[t], ...)`` over the same keys: padding steps are skipped by
    a batch-uniform ``lax.cond`` whose live branch compiles to exactly the
    unpadded solve (see :meth:`~repro.core.grid.TimeGrid.padded_uniform`).
    One executable serves every horizon on the rung; ``t1`` is ignored in
    this mode (the window is ``t0 + n_steps*step_size`` padded).  Fixed-grid
    solves only — no ``save_every``/``save_at``/adaptive options.
    """
    leaf = jax.tree_util.tree_leaves(tick_keys)[0]
    # A typed key array ((T, B)-shaped, prng_key dtype) carries no trailing
    # key-data axis; raw uint32 keys do — so a flat single-tick batch is
    # rank 1 typed / rank 2 raw, and must go to sdeint instead.
    typed = jnp.issubdtype(leaf.dtype, jax.dtypes.prng_key)
    if leaf.ndim < (2 if typed else 3):
        raise ValueError(
            f"tick_keys must stack per-tick key batches — expected a "
            f"(n_ticks, batch, ...) key array, got shape {tuple(leaf.shape)} "
            f"(dtype {leaf.dtype}); for a single flat batch call "
            "sdeint(..., batch_keys=keys)"
        )

    if active_steps is not None:
        if step_size is None:
            raise ValueError(
                "active_steps (padded bucketed dispatch) requires step_size "
                "— the bucket's exact static step h shared by every tick"
            )
        active = jnp.asarray(active_steps, jnp.int32)
        if active.ndim != 1 or active.shape[0] != leaf.shape[0]:
            raise ValueError(
                f"active_steps must be a (n_ticks,) = ({leaf.shape[0]},) "
                f"int array (one live-step count per tick), got shape "
                f"{tuple(active.shape)}"
            )
        one = jax.vmap(_padded_trajectory_fn(term, solver, t0, n_steps, y0,
                                             float(step_size), **kwargs),
                       in_axes=(0, None))
        operands = (tick_keys, active)
    else:
        if step_size is not None:
            raise ValueError("step_size only applies with active_steps "
                             "(padded bucketed dispatch)")
        one = jax.vmap(_trajectory_fn(term, solver, t0, t1, n_steps, y0,
                                      **kwargs))
        operands = (tick_keys,)
    batched = _batched_fn(one, leaf.shape[1], mesh, mesh_axis,
                          n_operands=len(operands))
    if leaf.shape[0] == 1:
        # Serving-tail fast path: a depth-1 stack needs no on-device tick
        # loop — run the single batch directly and restore the tick axis.
        # Bitwise-identical: the lax.map body below is this same batched fn,
        # and per-tick bits are key-determined (regression-tested).
        out = batched(*jax.tree_util.tree_map(lambda x: x[0], operands))
        return jax.tree_util.tree_map(lambda x: x[None], out)
    return jax.lax.map(lambda ops: batched(*ops), operands)


def _resolve(term, solver, y0, adjoint, noise_shape, dtype, *,
             adaptive=False, rtol=None, atol=None, h0=None, bm_tol=None,
             bounded=True, why=""):
    """The checks and defaults every single-trajectory builder shares.

    Resolves ``solver``, rejects an unknown adjoint and, on a fixed grid, any
    adaptive-only option that is set (``why`` ends that message), and fills
    in ``noise_shape`` and ``dtype`` from the term and ``y0``.  Returns
    ``(solver, adaptive, noise_shape, dtype)``, with ``adaptive`` true also
    for an ``:adaptive`` solver spec.
    """
    solver = get_solver(solver)
    adaptive = adaptive or getattr(solver, "adaptive", False)
    if adjoint not in ("full", "recursive", "reversible"):
        raise ValueError(f"unknown adjoint {adjoint!r}")
    if not adaptive:
        for opt_name, bad in (("rtol", rtol is not None),
                              ("atol", atol is not None),
                              ("h0", h0 is not None),
                              ("bm_tol", bm_tol is not None),
                              ("bounded", bounded is not True)):
            if bad:
                raise ValueError(
                    f"{opt_name} only applies to adaptive solves{why}")
    if noise_shape is None:
        noise_shape = _infer_noise_shape(term, y0)
    if dtype is None:
        dtype = _infer_dtype(y0)
    return solver, adaptive, noise_shape, dtype


def _trajectory_fn(
    term, solver, t0, t1, n_steps, y0, *, args=None, adjoint="full",
    save_every=None, remat_chunk=None, adaptive=False, save_at=None,
    rtol=None, atol=None, h0=None, bm_tol=None, bounded=True,
    bulk_increments=True, guard=None, noise_shape=None, dtype=None,
):
    """Validate options and build the single-trajectory ``key -> result`` fn
    (shared by :func:`sdeint` and :func:`sdeint_ticks`)."""
    solver, adaptive, noise_shape, dtype = _resolve(
        term, solver, y0, adjoint, noise_shape, dtype, adaptive=adaptive,
        rtol=rtol, atol=atol, h0=h0, bm_tol=bm_tol, bounded=bounded,
        why="; pass adaptive=True or an ':adaptive' solver spec — a "
            "tolerance request must not silently run a fixed grid")
    if adaptive and not bounded and adjoint != "full":
        raise ValueError(
            f"bounded=False (single controller pass) is forward-only and "
            f"cannot host the {adjoint!r} adjoint; use bounded=True "
            "(realize-then-solve) for gradients"
        )
    if adaptive and save_every is not None:
        raise ValueError(
            "save_every indexes a fixed grid; with adaptive=True pass "
            "save_at=<array of times> instead"
        )
    if save_at is not None and not adaptive:
        raise ValueError(
            "save_at (arbitrary-time dense output) requires adaptive=True / "
            "an ':adaptive' solver spec; on a fixed grid use save_every"
        )

    if adaptive:
        tols = {}
        if rtol is not None:
            tols["rtol"] = rtol
        if atol is not None:
            tols["atol"] = atol

        def one(k):
            vbt = virtual_brownian_tree(
                k, t0, t1, shape=noise_shape, dtype=dtype, tol=bm_tol
            )
            return integrate_adaptive(
                solver, term, y0, vbt, args, t0=t0, t1=t1,
                h0=h0, max_steps=int(n_steps), save_at=save_at,
                bounded=bounded, adjoint=adjoint, remat_chunk=remat_chunk,
                bulk_increments=bulk_increments, guard=guard,
                **tols,
            )
    else:
        def one(k):
            bm = brownian_path(k, t0, t1, n_steps, shape=noise_shape, dtype=dtype)
            return solve(
                solver, term, y0, bm, args,
                adjoint=adjoint, save_every=save_every, remat_chunk=remat_chunk,
                bulk_increments=bulk_increments, guard=guard,
            )

    return one


def _padded_trajectory_fn(
    term, solver, t0, n_padded, y0, h, *, args=None, adjoint="full",
    save_every=None, remat_chunk=None, adaptive=False, save_at=None,
    rtol=None, atol=None, h0=None, bm_tol=None, bounded=True,
    bulk_increments=True, guard=None, noise_shape=None, dtype=None,
):
    """Build the padded single-trajectory ``(key, n_active) -> result`` fn
    for bucketed dispatch: ``h`` is the bucket's exact static step size,
    ``n_padded`` its ladder rung, ``n_active`` the (traced, batch-uniform)
    true step count of one tick."""
    solver, adaptive, noise_shape, dtype = _resolve(
        term, solver, y0, adjoint, noise_shape, dtype, adaptive=adaptive,
        rtol=rtol, atol=atol, h0=h0, bm_tol=bm_tol, bounded=bounded,
        why=", which cannot run under padded bucketed dispatch")
    if adaptive:
        raise ValueError(
            "active_steps (padded bucketed dispatch) applies to fixed-grid "
            "solves only; adaptive requests must dispatch exact"
        )
    if save_every is not None or save_at is not None:
        raise ValueError(
            "padded bucketed dispatch carries no saved trajectories; "
            "save_every/save_at requests must dispatch exact"
        )

    def one(k, n_active):
        bm = padded_brownian_path(k, t0, h, n_padded, shape=noise_shape,
                                  dtype=dtype)
        grid = TimeGrid.padded_uniform(t0, h, n_active, n_padded, bm)
        return solve(solver, term, y0, grid, args, adjoint=adjoint,
                     remat_chunk=remat_chunk,
                     bulk_increments=bulk_increments, guard=guard)

    return one


def _batched_fn(batched, n_batch: int, mesh, mesh_axis, n_operands: int = 1):
    """Wrap a vmap'd trajectory batch in shard_map when a mesh axis is named.

    ``n_operands > 1``: the batch fn takes extra *replicated* operands after
    the sharded key batch (the padded path's batch-uniform ``n_active``)."""
    if mesh_axis is None:
        if mesh is not None:
            raise ValueError("mesh given without mesh_axis; name the axis to shard over")
        return batched

    from jax.sharding import PartitionSpec as P

    mesh = mesh if mesh is not None else _ambient_mesh()
    axis_size = mesh.shape[mesh_axis]
    if n_batch % axis_size != 0:
        raise ValueError(
            f"mesh axis {mesh_axis!r} of size {axis_size} does not divide "
            f"the batch of {n_batch} trajectories"
        )
    spec = P(mesh_axis)
    in_specs = spec if n_operands == 1 else \
        (spec,) + (P(),) * (n_operands - 1)
    return jax.shard_map(batched, mesh=mesh, in_specs=in_specs,
                         out_specs=spec, check_vma=False)
