"""One generalized ``solve()`` over any :class:`~repro.core.grid.TimeGrid`,
under the paper's three adjoints.

* **Full** (discretise-then-optimise): plain autodiff through ``lax.scan``;
  exact gradients of the discrete computation, O(n) activation memory.
* **Recursive** (checkpointed): segments of the scan are rematerialised
  (``jax.checkpoint``), giving the O(sqrt(n)) memory/compute trade.
* **Reversible**: O(1) memory.  The backward pass *reconstructs* the forward
  trajectory with the solver's algebraic reverse step (exact for Reversible
  Heun / MCF; O(h^{m+1})-accurate for EES(2,m)) and re-plays each step under
  ``jax.vjp`` — Algorithm 1 of the paper (and, composed with the CF-EES step
  on a manifold, Algorithm 2: the stage adjoints live on the cotangent bundle
  automatically because every group action is an ordinary JAX computation).

All three run over the *same* grid abstraction: a uniform grid (the classic
fixed-grid solve — the static step size compiles to exactly the computation
this module always ran) or an adaptively **realized** grid from
:func:`repro.core.adaptive.realize_grid` — per-step ``(t, h[n], dW[n])``
triples with zero-length padding steps masked out.  Since PR 4 the noise is
**bulk-realized** by default: every ``dW[n]`` is generated in one batched
driver pass before the scan (:meth:`~repro.core.grid.TimeGrid.increments`)
and streamed out of the buffer on the forward *and* reversible-backward
sweeps — bit-identical increments, with all per-step RNG hoisted out of
the sequential hot loop (``bulk_increments=False`` restores per-step
generation).  Reversibility never
needed uniform steps, only that the backward pass replays the same step
sequence; the grid's ``ts`` array pins that down, and the bitwise-
reproducible drivers make every ``dW[n]`` recomputable in O(1) memory during
the backward sweep.  Step rejection happened at realization time, so the
two-register reverse step needs no third (3S*) register.

The forward sweep is written once (``_forward``): every adjoint runs the same
scan of the same step body, and they differ only in what the backward pass
keeps of it — all of it (full), its chunk boundaries (recursive), or only the
final solver state (reversible).  So ``y_final``, the saved trajectory and the
guard's ``diverged`` flag are bitwise identical across adjoints.  Saved
trajectories come in two forms: ``save_every`` (every k-th step, fixed grids)
and ``save_at`` (dense output linearly interpolated onto an arbitrary time
grid — any grid, with the cotangents of each save point injected along the
reversible backward sweep).
"""
from __future__ import annotations

import math
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .brownian import BrownianPath
from .grid import TimeGrid, fill_saves, save_mask
from .pytree import tree_add, tree_blowup, tree_select
from .solvers import _PrediffusedTerm

__all__ = ["SolveResult", "solve"]


class SolveResult(NamedTuple):
    y_final: Any
    ys: Any  # (n_saves, ...) pytree of saved states, or None
    # Scalar bool (per vmap lane): did the state ever go non-finite or exceed
    # the guard threshold during the solve?  None when the guard is off.
    diverged: Any = None


def _float0_like(tree):
    """Zero cotangents for a pytree that may contain non-inexact leaves."""

    def z(x):
        if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.inexact):
            return jnp.zeros_like(x)
        return np.zeros(jnp.shape(x), dtype=jax.dtypes.float0)

    return jax.tree_util.tree_map(z, tree)


def _ct_add(a, b):
    def add(x, y):
        if hasattr(x, "dtype") and x.dtype == jax.dtypes.float0:
            return x
        return x + y

    return jax.tree_util.tree_map(add, a, b)


def _ct_mask(live, ct):
    """Zero a cotangent pytree where ``live`` is False (float0 passes through)."""

    def m(x):
        if hasattr(x, "dtype") and x.dtype == jax.dtypes.float0:
            return x
        return jnp.where(live, x, jnp.zeros_like(x))

    return jax.tree_util.tree_map(m, ct)


def _segment_counts(n_steps: int, save_every: Optional[int]):
    if save_every is None:
        return 1, n_steps
    if n_steps % save_every != 0:
        raise ValueError(f"n_steps={n_steps} not divisible by save_every={save_every}")
    return n_steps // save_every, save_every


def _as_grid(grid) -> TimeGrid:
    if isinstance(grid, TimeGrid):
        return grid
    if isinstance(grid, BrownianPath):
        return TimeGrid.from_path(grid)
    raise TypeError(
        f"solve() integrates over a TimeGrid (or a BrownianPath, wrapped "
        f"automatically); got {type(grid).__name__} — build one with "
        "TimeGrid.uniform(...) or realize_grid(...)"
    )


def _save_consts(grid: TimeGrid, save_at):
    """(save_ts, eps_end, h_floor) — same constants the realization loop uses,
    so realized-grid dense output is bitwise-identical to the single-pass
    accept/reject fill."""
    save_ts = jnp.asarray(save_at, jnp.result_type(float))
    if save_ts.ndim != 1:
        raise ValueError(f"save_at must be 1-D, got shape {save_ts.shape}")
    span = grid.t1 - grid.t0
    return save_ts, 1e-9 * span, 1e-7 * span


def _broadcast_saves(y0, n_saves: int):
    return jax.tree_util.tree_map(
        lambda l: jnp.broadcast_to(l, (n_saves,) + jnp.shape(l)), y0
    )


def _make_stepper(solver, term, grid: TimeGrid, args, masked, dWs):
    """One grid step ``((state, w), n) -> ((new_state, w_next), (t, h))``;
    zero-length padding steps of a realized grid are a no-op.

    ``dWs`` (the default — see :meth:`~repro.core.grid.TimeGrid.increments`)
    is the bulk Brownian realization: every step's increment was generated in
    one batched pass before the scan, and the step body just streams row
    ``n`` out of the buffer — no per-step threefry or tree descent inside
    the sequential loop.  With ``dWs=None`` the pre-bulk paths are kept:
    when the driver supports point evaluation (a Virtual Brownian Tree), the
    forward sweeps *stream* the path — ``w`` carries ``W(ts[n])`` so each
    step costs one tree descent instead of the two a fresh
    ``increment_over`` query pays — and otherwise each step queries
    ``grid.increment(n)``.  All three increment sources produce
    bitwise-identical increments (``weval``/``fold_in`` are pure functions
    of their inputs).  Returns ``(init_w, step)``; ``init_w()`` builds the
    initial carry element.
    """
    driver = grid.driver
    needs_levy = getattr(solver, "needs_levy_area", False)

    def init_w():
        return None

    if dWs is not None:
        # For Levy-area solvers the buffer is the stacked (dWs, dHs) pair
        # (see TimeGrid.levy_increments); row n is taken from the pytree.
        def increment(n, w):
            return jax.tree_util.tree_map(lambda x: x[n], dWs), w
    elif driver is not None and hasattr(driver, "weval"):
        def init_w():
            return driver.weval(grid.ts[0])

        def increment(n, w):
            w_next = driver.weval(grid.ts[n + 1])
            dW = jax.tree_util.tree_map(jnp.subtract, w_next, w)
            if needs_levy:
                dW = (dW, driver.levy_area(grid.ts[n], grid.ts[n + 1]))
            return dW, w_next
    else:
        def increment(n, w):
            if needs_levy:
                return grid.levy_increment(n), w
            return grid.increment(n), w

    def step(carry, n):
        state, w = carry
        t, h = grid.t_of(n), grid.h_of(n)
        dW, w = increment(n, w)
        new = solver.step(term, state, t, h, dW, args)
        if masked:
            new = tree_select(h > 0, new, state)
        return (new, w), (t, h)

    if getattr(grid, "is_padded", False):
        # Padded-uniform grids (bucketed dispatch): skip steps at or past
        # n_active with a lax.cond.  The predicate is a batch-uniform scalar
        # — one n_active per grid, shared by every vmap lane — so it stays a
        # real conditional under vmap: dead padding steps genuinely skip the
        # solver body, and the live branch is its own computation, compiled
        # exactly as the unpadded solve loop (a tree_select over both
        # branches would change XLA's fusion of multi-register steps and
        # drift the last bits; the cond provably does not —
        # regression-tested bitwise across the solver zoo).
        inner_step = step
        n_active = grid.n_active

        def step(carry, n):
            return jax.lax.cond(
                n < n_active,
                lambda: inner_step(carry, n),
                lambda: (carry, (grid.t_of(n), grid.h_of(n))),
            )

    return init_w, step


# ---------------------------------------------------------------------------
# The forward sweep of all three adjoints (full & recursive differentiate it).
# ---------------------------------------------------------------------------

def _scan_steps(body, carry, n0, length, remat_chunk):
    """Scan ``body`` over the ``length`` step indices from ``n0`` (from 0
    when ``n0`` is None), in ``jax.checkpoint``-ed chunks of
    ``remat_chunk`` steps when that is set."""

    def from_n0(idx):
        return idx if n0 is None else n0 + idx

    if remat_chunk is None:
        carry, _ = jax.lax.scan(body, carry, from_n0(jnp.arange(length)))
        return carry
    if length % remat_chunk != 0:
        raise ValueError(f"{length} steps are not divisible by "
                         f"remat_chunk={remat_chunk}")

    @jax.checkpoint
    def chunk(carry, c0):
        carry, _ = jax.lax.scan(body, carry, c0 + jnp.arange(remat_chunk))
        return carry, None

    carry, _ = jax.lax.scan(
        chunk, carry, from_n0(remat_chunk * jnp.arange(length // remat_chunk)))
    return carry


@jax.named_scope("sde_forward")
def _forward(solver, term, y0, grid: TimeGrid, args, save_every, save_at,
             dWs, guard, remat_chunk=None):
    """THE forward sweep of every adjoint: ``(state_f, ys, diverged)``, with
    ``state_f`` the solver state before ``extract`` (the reversible backward
    starts from it).  One spelling is what keeps ``ys`` bitwise-identical
    across adjoints.

    The guard only *observes*: the step scan is the exact unguarded program
    (guarded results stay bitwise-identical), and it reduces at save-segment
    boundaries, or after the loop over the final state and the save buffer
    under ``save_at``.  Non-finites persist once they enter the state and a
    genuine blow-up stays above any threshold, so those checks detect every
    blow-up a per-step check would, at ~1/seg_len the overhead.
    """
    masked = not grid.is_uniform
    init_w, step = _make_stepper(solver, term, grid, args, masked, dWs)
    state0 = solver.init(term, grid.t0, y0, args)

    if save_at is not None:
        # Dense output on an arbitrary time grid: one flat scan carrying the
        # save buffer, filled by whichever step covers each save time.
        save_ts, eps_end, h_floor = _save_consts(grid, save_at)

        def one(carry, n):
            sw, ys = carry
            new_sw, (t, h) = step(sw, n)
            live = (h > 0) if masked else True
            ys = fill_saves(ys, save_ts, live, t, grid.ts[n + 1],
                            solver.extract(sw[0]), solver.extract(new_sw[0]),
                            grid.t1, eps_end, h_floor)
            return (new_sw, ys), None

        carry0 = ((state0, init_w()), _broadcast_saves(y0, len(save_at)))
        (state_f, _), ys = _scan_steps(one, carry0, None, grid.n_steps,
                                       remat_chunk)
        div = None
        if guard is not None:
            div = (tree_blowup(solver.extract(state_f), guard)
                   | tree_blowup(ys, guard))
        return state_f, ys, div

    n_seg, seg_len = _segment_counts(grid.n_steps, save_every)

    def one_step(carry, n):
        return step(carry, n)[0], None

    def segment(carry, n0):
        sw, div = carry
        sw = _scan_steps(one_step, sw, n0, seg_len, remat_chunk)
        if guard is not None:
            div = div | tree_blowup(solver.extract(sw[0]), guard)
        return (sw, div), (solver.extract(sw[0]) if save_every else None)

    div0 = None if guard is None else jnp.asarray(False)
    ((state_f, _), div), ys = jax.lax.scan(
        segment, ((state0, init_w()), div0), seg_len * jnp.arange(n_seg))
    return state_f, (ys if save_every else None), div


def _solve_scan(solver, term, y0, grid: TimeGrid, args, save_every, remat_chunk,
                save_at, dWs, guard):
    """Full adjoint (plain autodiff through the forward scan), or recursive
    with ``remat_chunk``: its chunks are rematerialised on the backward."""
    state_f, ys, div = _forward(solver, term, y0, grid, args, save_every,
                                save_at, dWs, guard, remat_chunk)
    return SolveResult(solver.extract(state_f), ys, div)


# ---------------------------------------------------------------------------
# Reversible adjoint (Algorithm 1 / 2).
# ---------------------------------------------------------------------------

def _solve_reversible(solver, term, y0, grid: TimeGrid, args, save_every,
                      save_at=None, dWs=None, guard=None, paths=False):
    """One custom vjp over one path, or with ``paths`` over a batch of them.

    ``paths``: ``dWs`` carries a leading path axis, ``y0`` and ``args`` are
    shared by every path, and ``grid`` is a uniform unpadded grid without a
    driver.  The per-path functions (step, reverse, extract, init) are
    vmapped over the path axis *inside* the custom vjp, so each reverse
    step's vjp is taken against the unbatched ``args`` and returns their
    cotangent already summed over paths: the backward carries one
    parameter-shaped sum, not one per path.
    """
    n_steps = grid.n_steps
    n_seg, seg_len = _segment_counts(n_steps, save_every)
    masked = not grid.is_uniform
    needs_levy = getattr(solver, "needs_levy_area", False)
    if save_at is not None:
        save_ts, eps_end, h_floor = _save_consts(grid, save_at)
    if paths:
        lift = jax.vmap

        def at_step(x, n):
            return x[:, n]
    else:
        def lift(f):
            return f

        def at_step(x, n):
            return x[n]

    def extract_vjp(s, c):
        return jax.vjp(solver.extract, s)[1](c)[0]

    def forward(grid, y0, args, dWs):
        # save_ts, already converted: no conversion inside the custom vjp
        return _forward(solver, term, y0, grid, args, save_every,
                        None if save_at is None else save_ts, dWs, guard)

    if paths:
        forward = jax.vmap(forward, in_axes=(None, None, None, 0))

    def run_fwd(grid, y0, args, dWs):
        state_f, ys, div = forward(grid, y0, args, dWs)
        return SolveResult(lift(solver.extract)(state_f), ys, div), (
            grid, state_f, args, dWs)

    @jax.custom_vjp
    def run(grid, y0, args, dWs):
        return run_fwd(grid, y0, args, dWs)[0]

    @jax.named_scope("sde_reverse")
    def run_bwd(res, ct):
        # The backward sweep streams the SAME bulk realization the forward
        # consumed (it is a residual, not recomputed): increments are read in
        # reverse order from the buffer, keeping the O(1)-in-trajectory
        # reconstruction while dropping the per-step driver recompute.
        grid, state_f, args, dWs = res
        ct_yf, ct_ys = ct.y_final, ct.ys

        # Inject the terminal cotangent through `extract`.
        ct_state = lift(extract_vjp)(state_f, ct_yf)
        ct_args = _float0_like(args)

        def body(carry, n):
            state, ct_state, ct_args = carry
            t, h = grid.t_of(n), grid.h_of(n)
            if dWs is None:
                dW = (grid.levy_increment(n) if needs_levy
                      else grid.increment(n))
            else:
                dW = jax.tree_util.tree_map(lambda x: at_step(x, n), dWs)
            live = (h > 0) if masked else True
            # 1. Reconstruct the pre-step state (O(h^{m+1}) drift for EES;
            #    exact for algebraically reversible solvers).  Padding steps
            #    were no-ops forward, so they are no-ops backward.
            prev = lift(lambda s, w: solver.reverse(term, s, t, h, w, args))(
                state, dW)
            if masked:
                prev = tree_select(live, prev, state)
            # 2. Cotangents of saved outputs produced by this step.
            pick_old = None
            if save_every is not None:
                is_save = (n + 1) % seg_len == 0
                idx = jnp.clip((n + 1) // seg_len - 1, 0, n_seg - 1)
                picked = jax.tree_util.tree_map(
                    lambda a: at_step(a, idx) * jnp.asarray(is_save, a.dtype),
                    ct_ys)
                ct_state = tree_add(ct_state,
                                    lift(extract_vjp)(state, picked))
            if save_at is not None:
                # Forward wrote ys[j] = y_old + frac_j (y_new − y_old) at the
                # saves covered by this step (save_mask is disjoint across
                # steps, so exactly one step injects each save's cotangent);
                # split it into its y_new part (through the post-step state,
                # now) and its y_old part (directly onto the reconstructed
                # state, below).
                t_new = grid.ts[n + 1]
                m = save_mask(save_ts, live, t, t_new, grid.t1, eps_end)
                frac = jnp.clip(
                    (save_ts - t) / jnp.maximum(t_new - t, h_floor), 0.0, 1.0)
                w_new, w_old = m * frac, m * (1.0 - frac)

                def pick(w, c):
                    return jnp.einsum("s,s...->...", w.astype(c.dtype), c)

                _, vex = jax.vjp(solver.extract, state)
                (inc,) = vex(jax.tree_util.tree_map(
                    lambda c: pick(w_new, c), ct_ys))
                ct_state = tree_add(ct_state, inc)
                pick_old = jax.tree_util.tree_map(
                    lambda c: pick(w_old, c), ct_ys)
            # 3. Re-play the step under vjp for exact local cotangents.  With
            #    ``paths`` the vjp is against the shared ``args``: its
            #    transpose contracts the path axis, once per step.
            def step_fn(s, a):
                return lift(lambda s1, w: solver.step(term, s1, t, h, w, a))(
                    s, dW)

            _, vjp = jax.vjp(step_fn, prev, args)
            ct_prev, ct_args_inc = vjp(ct_state)
            if masked:
                ct_prev = tree_select(live, ct_prev, ct_state)
                ct_args_inc = _ct_mask(live, ct_args_inc)
            if pick_old is not None:
                _, vex_prev = jax.vjp(solver.extract, prev)
                (inc_prev,) = vex_prev(pick_old)
                ct_prev = tree_add(ct_prev, inc_prev)
            return (prev, ct_prev, _ct_add(ct_args, ct_args_inc)), None

        if getattr(grid, "is_padded", False):
            # Padding steps were skipped forward (lax.cond in the stepper);
            # skip them backward the same way — the carry passes through
            # untouched, so reconstruction and cotangents see only the live
            # prefix (same batch-uniform predicate, same bitwise guarantee).
            inner_body = body

            def body(carry, n):
                return jax.lax.cond(
                    n < grid.n_active,
                    lambda: inner_body(carry, n),
                    lambda: (carry, None),
                )

        (state0_rec, ct_state0, ct_args), _ = jax.lax.scan(
            body, (state_f, ct_state, ct_args), jnp.arange(n_steps - 1, -1, -1)
        )

        # Back out through `init` (matters for solvers whose init evaluates
        # the vector field, e.g. Reversible Heun).
        y0_rec = lift(solver.extract)(state0_rec)

        def init_fn(y, a):
            return lift(lambda y1: solver.init(term, grid.t0, y1, a))(y)

        _, vjp0 = jax.vjp(init_fn, y0_rec, args)
        ct_y0, ct_args_inc = vjp0(ct_state0)
        ct_args = _ct_add(ct_args, ct_args_inc)
        if paths:
            # every path started from the one shared y0
            ct_y0 = jax.tree_util.tree_map(lambda c: jnp.sum(c, 0), ct_y0)
        if save_at is not None:
            # Save entries no step covered (at/before t0, or past where a
            # budget-exhausted realization stopped) still hold the broadcast
            # initial state — their cotangents flow straight to y0.  Exact
            # complement of the per-step save_mask coverage: the eps slack
            # exists only when the grid actually reached t1.
            t_final = grid.ts[-1]
            slack = jnp.where(t_final >= grid.t1 - eps_end, eps_end, 0.0)
            w0 = (save_ts <= grid.t0) | (save_ts > t_final + slack)
            ct_y0 = jax.tree_util.tree_map(
                lambda cy, c: cy + jnp.einsum(
                    "s,s...->...", w0.astype(c.dtype), c),
                ct_y0, ct_ys)
        # The grid is data: zero cotangents for ts/hs, the driver's key, and
        # the bulk noise buffer.
        return (_float0_like(grid), ct_y0, ct_args, _float0_like(dWs))

    run.defvjp(run_fwd, run_bwd)
    return run(grid, y0, args, dWs)


# ---------------------------------------------------------------------------
# Public entry point.
# ---------------------------------------------------------------------------

def _maybe_prediffuse(solver, term, y0, grid, args, adjoint, dWs):
    """Additive-noise fast path: hoist the diffusion out of the scan.

    With ``noise="additive"`` the diffusion matrix is independent of ``t``
    and ``y`` (the additive contract — it may still depend on ``args``), so
    ``g * dW[n]`` can be computed for every step in ONE broadcast pass over
    the bulk Brownian buffer instead of re-evaluating ``g`` inside the
    sequential loop.  The substituted :class:`_PrediffusedTerm` then combines
    ``f * h + w`` per step — the same IEEE multiply, hoisted, so results and
    gradients are bitwise-equal to the standard route.

    Excluded cases keep their general route:

    * ``adjoint="reversible"`` — its backward pass returns zero cotangents
      for the noise buffer (it is data), so gradients through a precomputed
      ``g(args) * dW`` buffer would cut the diffusion-parameter cotangents.
    * per-step generation (``dWs is None``) — nothing to hoist over.
    * solvers that read ``term.diffusion`` directly (Milstein, SRK) or
      consume Levy-area pairs — the buffer layout is not a plain increment.
    """
    if (
        dWs is None
        or getattr(term, "noise", None) != "additive"
        or adjoint not in ("full", "recursive")
        or getattr(solver, "needs_levy_area", False)
        or getattr(solver, "needs_diffusion", False)
    ):
        return term, dWs
    g0 = term.diffusion(grid.t0, y0, args)
    ws = jax.tree_util.tree_map(lambda gi, wi: gi * wi, g0, dWs)
    return _PrediffusedTerm(base=term), ws


def solve(
    solver,
    term,
    y0,
    grid,
    args=None,
    *,
    adjoint: str = "full",
    save_every: Optional[int] = None,
    save_at=None,
    remat_chunk: Optional[int] = None,
    bulk_increments: bool = True,
    guard: Optional[float] = None,
) -> SolveResult:
    """Integrate ``term`` over ``grid`` with ``solver`` — THE solve loop.

    Every integration in the repo bottoms out here: fixed uniform grids,
    matched-driver grids over a Virtual Brownian Tree, and adaptively
    realized (non-uniform) grids all run the same forward scan, under the
    same three adjoints — one forward sweep for all three, which differ
    only in their backward pass.

    Parameters
    ----------
    solver:
        A solver *object* (``init`` / ``step`` / ``reverse`` / ``extract``)
        — resolve spec strings first with
        :func:`~repro.core.registry.get_solver`, or use
        :func:`~repro.core.sdeint.sdeint`, which owns that plumbing.
    term:
        :class:`~repro.core.solvers.SDETerm` (or a manifold term for CF-EES
        solvers).
    y0:
        Initial state pytree.
    grid:
        A :class:`~repro.core.grid.TimeGrid` — uniform
        (``TimeGrid.uniform(t0, t1, n, driver)``) or realized
        (:func:`~repro.core.adaptive.realize_grid`); a fixed-grid
        :class:`~repro.core.brownian.BrownianPath` is accepted directly and
        wrapped.  Zero-length padding steps of a realized grid are masked to
        no-ops in every adjoint.
    args:
        Passed to the drift/diffusion callables.
    adjoint:
      * ``"full"``       — O(n) memory, exact discrete gradients.
      * ``"recursive"``  — remat at ``remat_chunk`` granularity (default
        ~sqrt(segment)), O(sqrt n) memory.
      * ``"reversible"`` — O(1) memory via reverse reconstruction along the
        grid — uniform or realized alike (the backward sweep replays the
        same ``(t, h[n], dW[n])`` sequence; rejection already happened at
        realization time, so no third register is needed).
    save_every:
        Saves ``extract(state)`` every that many steps (must divide
        ``n_steps``; on a realized grid this counts padded trial slots, so
        prefer ``save_at`` there).  Mutually exclusive with ``save_at``.
    save_at:
        1-D array of output times: dense output linearly interpolated
        between the grid steps covering each time, under every adjoint
        (reversible injects each save cotangent during the backward sweep).
        Entries at or before ``t0`` (or beyond a budget-exhausted grid's
        end) hold ``y0``.
    bulk_increments:
        ``True`` (default): realize every step's Brownian increment in ONE
        batched driver pass before the scan
        (:meth:`~repro.core.grid.TimeGrid.increments` — stacked threefry /
        one batched level-sweep) and stream rows out of the buffer on both
        the forward and the reversible-backward sweeps.  The increments are
        bit-identical to the per-step draws; results and gradients match
        the per-step path to ulp-level (the scan body is a different XLA
        program, so FMA scheduling may differ in the last bit — all
        *within-mode* reproducibility guarantees are exact).  Trades
        O(n_steps x noise_shape) buffer memory for hoisting all RNG out of
        the sequential hot loop.  ``False`` restores per-step generation
        (the pre-PR-4 behavior — e.g. when the noise buffer itself would
        not fit).
    guard:
        Blow-up guard threshold.  When set, the state is checked at every
        save-segment boundary (non-finite entries, or any ``|y| > guard``;
        every ``save_every`` steps, or once at the solve's end when nothing
        is saved) and the OR of those checks is carried through the scan and
        returned as ``SolveResult.diverged`` — a scalar device bool per
        solve (per vmap lane under ``sdeint``), with no host sync.
        Boundary granularity loses nothing: non-finites persist once they
        enter the state and a genuine blow-up stays above any threshold, so
        every divergence the per-step check would flag reaches a boundary —
        while clean traffic pays one extra reduce per segment instead of
        per step (< 5% drain throughput, gated in CI).  ``float('inf')``
        checks non-finiteness only.  The guard is a pure observer: the step
        computation path is untouched, so guarded results are
        bitwise-identical to unguarded ones.  ``None`` (default) disables
        the check (``diverged`` is ``None``).

    Returns
    -------
    :class:`SolveResult` — ``y_final`` (state at the grid's end), ``ys``
    (the saved trajectory: ``(n_steps/save_every, ...)`` or
    ``(len(save_at), ...)``, or ``None``) and ``diverged`` (scalar bool when
    ``guard`` is set, else ``None``).

    Example
    -------
    >>> grid = TimeGrid.uniform(0.0, 1.0, 1000, brownian_path(key, 0.0, 1.0,
    ...                                                       1000, shape=(4,)))
    >>> out = solve(get_solver("ees25"), term, jnp.ones(4), grid, params,
    ...             adjoint="reversible")
    >>> out.y_final.shape
    (4,)
    """
    grid = _as_grid(grid)
    if save_at is not None and save_every is not None:
        raise ValueError("save_every and save_at are mutually exclusive")
    if grid.is_padded and (save_every is not None or save_at is not None):
        raise ValueError(
            "padded-uniform grids (bucketed dispatch) carry no saved "
            "trajectories — save_every/save_at requests must run on an "
            "exact (unpadded) grid"
        )
    if remat_chunk is not None and adjoint != "recursive":
        raise ValueError(
            f"remat_chunk configures the recursive adjoint's checkpoint "
            f"granularity and has no effect under adjoint={adjoint!r} — "
            "drop it or use adjoint='recursive'"
        )
    needs_levy = getattr(solver, "needs_levy_area", False)
    if bulk_increments:
        with jax.named_scope("sde_brownian"):
            dWs = grid.levy_increments() if needs_levy else grid.increments()
    else:
        dWs = None
    term, dWs = _maybe_prediffuse(solver, term, y0, grid, args, adjoint, dWs)
    if adjoint == "recursive" and remat_chunk is None:
        seg = save_every if save_every is not None else grid.n_steps
        remat_chunk = max(1, int(math.isqrt(seg)))
        while seg % remat_chunk != 0:
            remat_chunk -= 1
    if adjoint in ("full", "recursive"):
        return _solve_scan(solver, term, y0, grid, args, save_every,
                           remat_chunk, save_at, dWs, guard)
    if adjoint == "reversible":
        return _solve_reversible(solver, term, y0, grid, args, save_every,
                                 save_at, dWs, guard)
    raise ValueError(f"unknown adjoint {adjoint!r}")


def solve_reversible_paths(solver, term, y0, grid, args, dWs, *,
                           save_every: Optional[int] = None,
                           guard: Optional[float] = None) -> SolveResult:
    """Reversible-adjoint solve of a batch of paths under ONE custom vjp.

    The paths share ``y0``, ``args`` and the uniform, unpadded ``grid``;
    ``dWs`` stacks each path's bulk Brownian realization (the rows of
    :meth:`~repro.core.grid.TimeGrid.increments`) on a leading path axis.
    Results gain that axis and are bitwise equal to ``jax.vmap`` of
    :func:`solve` over the paths.  Gradients agree to rounding: the backward
    sweep sums the ``args`` cotangent over paths inside every reverse step's
    vjp, where the vmapped :func:`solve` keeps one sum per path and adds them
    up after the sweep.
    """
    grid = _as_grid(grid)
    if not grid.is_uniform or grid.is_padded:
        raise ValueError("a batch of reversible paths needs a uniform, "
                         "unpadded grid")
    return _solve_reversible(solver, term, y0, grid, args, save_every,
                             None, dWs, guard, paths=True)
