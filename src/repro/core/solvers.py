"""Euclidean SDE solvers: EES Runge-Kutta (Butcher + Williamson 2N forms),
Reversible Heun, and McCallum-Foster reversible couplings.

SDEs ``dy = f(y) dt + g(y) o dW`` are treated as RDEs driven by X = (t, W):
a Runge-Kutta tableau is applied with the vector-field increment

    F(t, y) . dX  =  f(t, y) h  +  g(t, y) . dW

in place of ``h f`` (the "simplified" Redmann-Riedel scheme, eq. (7)).  For
Brownian drivers this yields strong order 1/2 and weak order 1; for smoother
drivers (e.g. fBm with H > 1/2) higher rates follow from Theorem B.3.

All solvers expose a uniform interface:

    state  = solver.init(term, t0, y0, args)
    state' = solver.step(term, state, t, h, dW, args)      # t -> t + h
    state  = solver.reverse(term, state', t, h, dW, args)  # undo that step
    y      = solver.extract(state)

``reverse`` is *exact* (algebraic) for ReversibleHeun and MCF, and accurate to
O(h^{m+1}) per step for EES(2,m) schemes (effective symmetry).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from .pytree import tree_add, tree_axpy, tree_scale, tree_sub, tree_zeros_like
from .tableaux import Tableau
from .williamson import EES25_2N, EES27_2N, LowStorage

# Fused step kernels (repro.kernels.sde_step): imported once at module level,
# never inside the step hot loop.
from repro.kernels.sde_step import ops as _fused_ops
from repro.kernels.williamson2n.ops import (
    williamson2n_update as _williamson2n_update)


def _rk_strong_orders(b, c):
    """Documented strong orders of a driver-weighted RK scheme, from b.c.

    The driver-weighted increment ``F.dX = f h + g dW`` makes the scheme's
    SDE limit a function of ``sum_i b_i c_i`` alone: 0 gives the Ito
    integral (Euler), 1/2 the Stratonovich one (every order->=2 scheme).
    Schemes with ``b.c = 1/2`` additionally reproduce the Milstein
    ``(1/2) g g' dW^2`` term through their stage evaluations, so they are
    strong order 1 for commutative (componentwise-diagonal / scalar) noise
    and order 1 for additive noise; ``b.c = 0`` stays at the Euler rates.
    General non-commutative noise is order 1/2 for all of them.
    """
    bc = float(sum(bi * ci for bi, ci in zip(b, c)))
    if abs(bc - 0.5) < 1e-12:
        return "stratonovich", {"diagonal": 1.0, "scalar": 1.0,
                                "additive": 1.0, "general": 0.5}
    if bc == 0.0:
        return "ito", {"diagonal": 0.5, "scalar": 0.5,
                       "additive": 1.0, "general": 0.5}
    return None, {"diagonal": 0.5, "scalar": 0.5,
                  "additive": 1.0, "general": 0.5}


def _resolve_use_kernels(use_kernels, use_kernel):
    """One boolean from the current flag and its pre-PR-4 spelling.

    An explicitly-set ``use_kernels`` wins (``get_solver`` overrides must be
    able to pin the fused path on/off against a config string using the old
    spelling); the legacy ``use_kernel`` applies only when the new flag was
    left at its ``None`` default.
    """
    if use_kernels is not None:
        return bool(use_kernels)
    if use_kernel is not None:
        return bool(use_kernel)
    return False

__all__ = [
    "SDETerm",
    "VALID_NOISE",
    "ButcherSolver",
    "LowStorageSolver",
    "ReversibleHeun",
    "MCFSolver",
    "Milstein",
    "SRKAdditive",
    "ees25_solver",
    "ees27_solver",
    # Re-exported from .pytree for backwards compatibility — the canonical
    # home of the pytree linear-algebra helpers is repro.core.pytree.
    "tree_add",
    "tree_scale",
    "tree_axpy",
    "tree_zeros_like",
]


# -- SDE term ----------------------------------------------------------------

#: Noise structures an :class:`SDETerm` may declare, from most to least
#: specialized: "none" (ODE), "scalar" (one shared channel), "additive"
#: (state/time-independent diffusion), "diagonal" (elementwise channels),
#: "general" (full (d, m) diffusion matrix).
VALID_NOISE = ("none", "diagonal", "additive", "scalar", "general")


@dataclasses.dataclass(frozen=True)
class SDETerm:
    """Drift + diffusion with a declared noise structure.

    noise:
      * "none"     — ODE; ``diffusion`` is ignored.
      * "diagonal" — ``diffusion(t,y,args)`` has the same pytree structure as
        ``y``; ``dW`` likewise; the product is elementwise.
      * "additive" — diagonal arithmetic, plus the *contract* that
        ``diffusion`` is independent of ``t`` and ``y`` (it may depend on
        ``args``, e.g. a learned constant).  Declaring it unlocks the bulk
        fast path: :func:`~repro.core.adjoint.solve` pre-weights the whole
        increment buffer ``g . dW`` in one pass and the step loop never
        evaluates ``diffusion`` again (bitwise-equal to the diagonal route).
      * "scalar"   — ONE Brownian channel shared by every state component:
        ``dW`` is a scalar, ``diffusion`` matches the state pytree, the
        product broadcasts.
      * "general"  — array state ``(..., d)``; ``diffusion`` returns
        ``(..., d, m)``; ``dW`` is ``(..., m)``.

    The mode is validated at construction (not mid-``combine``, mid-jit) so a
    typo fails with the offending name before any tracing starts.
    """

    drift: Callable[..., Any]
    diffusion: Optional[Callable[..., Any]] = None
    noise: str = "diagonal"

    def __post_init__(self):
        if self.noise not in VALID_NOISE:
            raise ValueError(
                f"unknown noise mode {self.noise!r} for SDETerm; valid modes: "
                + ", ".join(repr(n) for n in VALID_NOISE)
            )
        if self.noise != "none" and self.diffusion is None:
            raise ValueError(
                f"SDETerm(noise={self.noise!r}) requires a diffusion callable; "
                "only noise='none' (ODE mode) may omit it"
            )

    def evals(self, t, y, args):
        """Vector-field evaluation, returned as a (f, g) pair."""
        f = self.drift(t, y, args)
        g = None if self.noise == "none" else self.diffusion(t, y, args)
        return f, g

    def combine(self, f, g, h, dW, use_kernels: bool = False):
        """f * h + g . dW  (the driver-weighted increment).

        ``use_kernels=True`` routes diagonal/additive/general noise through
        the fused :mod:`repro.kernels.sde_step` op (single pass on TPU,
        ``ref.py``-twin arithmetic elsewhere); the default path is the classic
        tree_map chain, bitwise-unchanged.  Additive noise shares the
        diagonal kernel (identical elementwise arithmetic); scalar noise
        stays on the plain path (its ``dW`` is a broadcast scalar).
        """
        if self.noise == "none" or g is None:
            return tree_scale(h, f)
        if use_kernels and self.noise in (
                "diagonal", "additive", "general"):
            kernel_noise = "diagonal" if self.noise == "additive" else self.noise
            return _fused_ops.tree_increment(f, g, dW, h, noise=kernel_noise)
        out = tree_scale(h, f)
        if self.noise in ("diagonal", "additive"):
            return jax.tree_util.tree_map(lambda o, gi, wi: o + gi * wi, out, g, dW)
        if self.noise == "scalar":
            return jax.tree_util.tree_map(lambda o, gi: o + gi * dW, out, g)
        return jax.tree_util.tree_map(
            lambda o, gi, wi: o + jnp.einsum("...dm,...m->...d", gi, wi), out, g, dW
        )

    def increment(self, t, y, args, h, dW, use_kernels: bool = False):
        f, g = self.evals(t, y, args)
        return self.combine(f, g, h, dW, use_kernels=use_kernels)


@dataclasses.dataclass(frozen=True)
class _PrediffusedTerm:
    """An additive-noise term whose diffusion increments were pre-weighted.

    Built by :func:`repro.core.adjoint.solve` when an ``"additive"`` term
    meets the bulk Brownian buffer under the full/recursive adjoints: the
    whole ``g . dW`` buffer is computed in ONE pass (``g`` is t/y-independent
    by the additive contract) and the per-step ``dW`` handed to solvers is
    *already* the diffusion increment — ``combine`` is just ``f*h + w``,
    one fewer operand stream per stage (see the ``"prediffused"`` fused
    kernel variants).  Bitwise-equal to the standard additive route: the
    multiply ``g*dW`` is the same IEEE multiply, merely hoisted out of the
    scan.
    """

    base: SDETerm
    noise: str = "prediffused"

    @property
    def drift(self):
        return self.base.drift

    def evals(self, t, y, args):
        f = self.base.drift(t, y, args)
        # Placeholder diffusion: ``combine`` ignores it (dW is pre-weighted),
        # but solvers that gate their fused path on ``g is None`` (and
        # Reversible Heun, which carries g in its scan state) need an array.
        return f, jax.tree_util.tree_map(jnp.ones_like, f)

    def combine(self, f, g, h, dW, use_kernels: bool = False):
        if use_kernels:
            return _fused_ops.tree_increment(f, None, dW, h, noise="prediffused")
        return jax.tree_util.tree_map(lambda fi, wi: fi * h + wi, f, dW)

    def increment(self, t, y, args, h, dW, use_kernels: bool = False):
        f = self.base.drift(t, y, args)
        return self.combine(f, None, h, dW, use_kernels=use_kernels)


# -- Butcher-form RK solver ---------------------------------------------------

class ButcherSolver:
    """Classical (s+1)N-register explicit RK applied to the (h, dW) driver.

    ``use_kernels=True`` fuses each memory-bound chain of the stage loop —
    the driver-weighted increment and the a/b-row axpy combinations — into
    single :mod:`repro.kernels.sde_step` passes (same arithmetic as the
    ``ref.py`` twins on non-TPU backends; the default path is bitwise the
    classic tree_axpy chain).
    """

    def __init__(self, tab: Tableau, use_kernels: bool = False):
        self.tab = tab
        self.name = tab.name
        self.evals_per_step = tab.stages
        self.is_reversible = tab.sym_order > tab.order  # effectively symmetric
        self.use_kernels = bool(use_kernels)
        self.sde_form, self.strong_orders = _rk_strong_orders(tab.b, tab.c)

    def init(self, term, t0, y0, args):
        return y0

    def extract(self, state):
        return state

    def _weighted(self, y, incrs, coeffs):
        """y + sum_i coeffs[i] * incrs[i], skipping zero coefficients."""
        live = [(c, k) for c, k in zip(coeffs, incrs) if c != 0.0]
        if not live:
            return y
        if self.use_kernels:
            return _fused_ops.tree_axpy_chain(
                y, [k for _, k in live], [c for c, _ in live])
        for c, k in live:
            y = tree_axpy(c, k, y)
        return y

    def _stages(self, term, state, t, h, dW, args):
        """Run the stage loop once; return (y_next, stage increments)."""
        tab = self.tab
        y = state
        incrs = []
        for i in range(tab.stages):
            yi = self._weighted(y, incrs, tab.a[i][:i])
            incrs.append(term.increment(t + tab.c[i] * h, yi, args, h, dW,
                                        use_kernels=self.use_kernels))
        out = self._weighted(y, incrs, tab.b)
        return out, incrs

    def step(self, term, state, t, h, dW, args):
        return self._stages(term, state, t, h, dW, args)[0]

    def step_with_error(self, term, state, t, h, dW, args):
        """One step plus an embedded first-order error estimate.

        The low-order companion is the Euler step built from the (already
        computed) first stage increment, so the estimate costs no extra
        vector-field evaluations; ``err = y_high - y_euler`` is an O(|dX|^2)
        local-error proxy (the (p, 1) embedded pair).
        """
        if self.tab.stages < 2:
            raise ValueError(
                f"{self.name} has a single stage: the high- and low-order "
                "solutions coincide, so there is no embedded error estimate "
                "(pick a >=2-stage scheme for adaptive stepping)"
            )
        out, incrs = self._stages(term, state, t, h, dW, args)
        y_low = tree_add(state, incrs[0])
        err = tree_sub(out, y_low)
        return out, err

    def reverse(self, term, state, t, h, dW, args):
        # Near-reversible reconstruction: the same scheme with negated driver
        # increments, started from the end of the step (time t + h).
        return self.step(term, state, t + h, -h, tree_scale(-1.0, dW), args)


# -- Williamson 2N solver ------------------------------------------------------

class LowStorageSolver:
    """Two-register Williamson form (eq. (2)): the paper's memory-optimal EES.

    ``use_kernels=True`` fuses the whole per-stage element stream — the
    driver-weighted increment ``k = f*h + g.dW`` *and* the two-register
    update — into one :mod:`repro.kernels.sde_step` pass per stage (Pallas on
    TPU, ``ref.py``-twin arithmetic elsewhere).  With no noise the stage
    falls back to the precomputed-``k`` ``kernels/williamson2n`` update.  The
    default path is bitwise the classic tree_axpy recurrence.
    """

    def __init__(self, ls: LowStorage, use_kernels: Optional[bool] = None,
                 use_kernel: Optional[bool] = None):
        self.ls = ls
        self.name = ls.name
        self.evals_per_step = ls.stages
        self.is_reversible = ls.sym_order > ls.order
        # `use_kernel` is the pre-PR-4 spelling, kept so existing spec
        # strings ("ees25:use_kernel=True") keep selecting the fused path.
        self.use_kernels = _resolve_use_kernels(use_kernels, use_kernel)
        # EES schemes are order 2 (b.c = 1/2): Stratonovich limit, order-1
        # strong rate for commutative noise (see _rk_strong_orders).
        self.sde_form = "stratonovich"
        self.strong_orders = {"diagonal": 1.0, "scalar": 1.0,
                              "additive": 1.0, "general": 0.5}

    def init(self, term, t0, y0, args):
        return y0

    def extract(self, state):
        return state

    def _update(self, a, b, delta, k, y):
        """delta' = a*delta + k ; y' = y + b*delta'  (optionally fused)."""
        if self.use_kernels:
            # Explicit flatten/unflatten: an is_leaf-on-tuples unzip would
            # misfire on states that are themselves tuples.
            d_leaves, treedef = jax.tree_util.tree_flatten(delta)
            pairs = [
                _williamson2n_update(d, kk, yy, a, b)
                for d, kk, yy in zip(d_leaves, treedef.flatten_up_to(k),
                                     treedef.flatten_up_to(y))
            ]
            delta2 = treedef.unflatten([p[0] for p in pairs])
            y2 = treedef.unflatten([p[1] for p in pairs])
            return delta2, y2
        delta2 = tree_axpy(a, delta, k)
        y2 = tree_axpy(b, delta2, y)
        return delta2, y2

    def _sweep(self, term, state, t, h, dW, args):
        """Run the 2N recurrence once; return (y_next, Y_{s-1}, K_s).

        The trailing pair costs nothing in ``step`` (Python references, no
        extra computation — unused outputs are dead-code-eliminated under
        jit) and is what the embedded estimator consumes.
        """
        ls = self.ls
        noise = getattr(term, "noise", "diagonal")
        # Additive noise shares the diagonal stage kernel (same elementwise
        # arithmetic); prediffused terms hit the cheaper f*h + w variant;
        # scalar noise stays on the plain path (its dW is a broadcast scalar).
        if noise == "additive":
            noise = "diagonal"
        fused = (self.use_kernels
                 and noise in ("diagonal", "general", "prediffused"))
        y = state
        delta = tree_zeros_like(y)
        y_prev = y
        k = None
        for l in range(ls.stages):
            y_prev = y
            if fused:
                f, g = term.evals(t + ls.c[l] * h, y, args)
                if g is None:
                    fused = False  # declared noise but no diffusion: plain path
                else:
                    delta_prev = delta
                    delta, y = _fused_ops.tree_ws_stage(
                        delta, y, f, g, dW, h, ls.A[l], ls.B[l], noise=noise)
                    # K_l = delta' - A_l * delta (for the embedded estimator;
                    # DCE'd in plain `step`).
                    k = tree_axpy(-ls.A[l], delta_prev, delta)
                    continue
            k = term.increment(t + ls.c[l] * h, y, args, h, dW,
                               use_kernels=self.use_kernels)
            delta, y = self._update(ls.A[l], ls.B[l], delta, k, y)
        return y, y_prev, k

    def step(self, term, state, t, h, dW, args):
        return self._sweep(term, state, t, h, dW, args)[0]

    def step_with_error(self, term, state, t, h, dW, args):
        """One 2N step plus the Appendix-D embedded first-order estimate.

        Store the second-to-last register state ``Y_{s-1}`` and advance it
        over the remaining fraction of the step with a single Euler update
        re-using the final stage evaluation::

            y_low = Y_{s-1} + (1 - c_s) * K_s,      err = y_{n+1} - y_low.

        No extra vector-field evaluations (the three-register variant of the
        paper's Limitations section).
        """
        y, y_prev, k_last = self._sweep(term, state, t, h, dW, args)
        c_last = self.ls.c[self.ls.stages - 1]
        y_low = tree_axpy(1.0 - c_last, k_last, y_prev)
        err = tree_sub(y, y_low)
        return y, err

    def reverse(self, term, state, t, h, dW, args):
        return self.step(term, state, t + h, -h, tree_scale(-1.0, dW), args)


# -- Reversible Heun (Kidger et al. 2021) --------------------------------------

class ReversibleHeun:
    """Algebraically reversible two-state Heun; one (f, g) evaluation per step.

    State: (y, yhat, f(t, yhat), g(t, yhat)).  Stability region is the segment
    lambda*h in [-i, i] (Theorem 2.1) — the instability the EES schemes fix.
    """

    name = "ReversibleHeun"
    evals_per_step = 1
    is_reversible = True
    # Trapezoidal in the driver (b.c = 1/2): Stratonovich limit.
    sde_form = "stratonovich"
    strong_orders = {"diagonal": 1.0, "scalar": 1.0,
                     "additive": 1.0, "general": 0.5}

    def __init__(self, use_kernels: bool = False):
        # Fused driver-weighted increments (repro.kernels.sde_step); the
        # algebraic reversibility argument only needs combine(-h, -dW) ==
        # -combine(h, dW), which holds exactly on the fused path too (IEEE
        # negation is exact).
        self.use_kernels = bool(use_kernels)

    def init(self, term, t0, y0, args):
        f, g = term.evals(t0, y0, args)
        if g is None:
            g = tree_zeros_like(f)
        return (y0, y0, f, g)

    def extract(self, state):
        return state[0]

    def step(self, term, state, t, h, dW, args):
        y, yh, fh, gh = state
        inc_prev = term.combine(fh, gh, h, dW, use_kernels=self.use_kernels)
        yh2 = tree_add(tree_sub(tree_scale(2.0, y), yh), inc_prev)
        f2, g2 = term.evals(t + h, yh2, args)
        if g2 is None:
            g2 = tree_zeros_like(f2)
        inc_next = term.combine(f2, g2, h, dW, use_kernels=self.use_kernels)
        y2 = tree_axpy(0.5, tree_add(inc_prev, inc_next), y)
        return (y2, yh2, f2, g2)

    def reverse(self, term, state, t, h, dW, args):
        # Exact: the scheme is its own inverse under (h, dW) -> (-h, -dW).
        return self.step(term, state, t + h, -h, tree_scale(-1.0, dW), args)


# -- McCallum-Foster reversible coupling ----------------------------------------

class MCFSolver:
    """Reversible coupling of an arbitrary base RK method (McCallum & Foster).

        y' = lam*y + (1-lam)*z + Psi_{dX}(z)
        z' = z - Psi_{-dX}(y')

    with exact algebraic inverse.  ``Psi_dX`` is the base-method increment over
    the driver increment dX = (h, dW).  Costs 2x the base stages per step.
    """

    def __init__(self, base: Tableau, lam: float = 0.999, name: Optional[str] = None,
                 use_kernels: bool = False):
        self.base = ButcherSolver(base, use_kernels=use_kernels)
        self.lam = lam
        self.name = name or f"MCF-{base.name}"
        self.evals_per_step = 2 * base.stages
        self.is_reversible = True
        self.use_kernels = self.base.use_kernels
        self.sde_form = self.base.sde_form
        self.strong_orders = self.base.strong_orders

    def _psi(self, term, z, t, h, dW, args):
        return tree_sub(self.base.step(term, z, t, h, dW, args), z)

    def init(self, term, t0, y0, args):
        return (y0, y0)

    def extract(self, state):
        return state[0]

    def step(self, term, state, t, h, dW, args):
        y, z = state
        lam = self.lam
        y2 = tree_add(
            tree_axpy(lam, y, tree_scale(1.0 - lam, z)),
            self._psi(term, z, t, h, dW, args),
        )
        ndW = tree_scale(-1.0, dW)
        z2 = tree_sub(z, self._psi(term, y2, t + h, -h, ndW, args))
        return (y2, z2)

    def reverse(self, term, state, t, h, dW, args):
        y2, z2 = state
        lam = self.lam
        ndW = tree_scale(-1.0, dW)
        z = tree_add(z2, self._psi(term, y2, t + h, -h, ndW, args))
        y = tree_scale(
            1.0 / lam,
            tree_sub(
                tree_sub(y2, tree_scale(1.0 - lam, z)),
                self._psi(term, z, t, h, dW, args),
            ),
        )
        return (y, z)


# -- Noise-specialized schemes -------------------------------------------------

class Milstein:
    """Milstein's method: Euler-Maruyama plus the first-order noise correction.

        y' = y + f h + g dW + (1/2) (g . grad g) (dW^2 - h)     [Ito]
        y' = y + f h + g dW + (1/2) (g . grad g) dW^2           [Stratonovich]

    ``g . grad g`` is computed exactly with one ``jax.jvp`` of the diffusion
    at tangent ``g``.  Strong order 1 for scalar noise (any ``g``), for
    diagonal noise whose channels are componentwise (``g_i`` depends on
    ``y_i`` only — the standard diagonal assumption), and trivially for
    additive noise (the correction vanishes identically, recovering
    order-1 Euler-Maruyama).  General (non-commutative) noise would need
    full Levy areas and is rejected up front with the offending mode named.

    ``form`` selects the Ito or Stratonovich correction; the two limits
    differ by the usual ``-(1/2) g g' h`` drift conversion.

    ``reverse`` subtracts the full Milstein increment evaluated at the step's
    endpoint — O(h^{3/2}) per-step reconstruction error.  (The naive
    negated-driver replay used by the RK schemes would NOT work here: the
    correction is even in ``dW``, so it fails to cancel at O(h).)  Prefer the
    full/recursive adjoints for training; the reversible adjoint runs but
    reconstructs with O(sqrt h) accumulated drift.
    """

    evals_per_step = 2  # one drift + one diffusion (the jvp re-uses the latter)
    is_reversible = False
    # Reads term.diffusion directly (for the jvp) — opt out of the
    # prediffused additive fast path (see adjoint._maybe_prediffuse).
    needs_diffusion = True
    #: documented strong convergence order per supported noise mode
    strong_orders = {"diagonal": 1.0, "scalar": 1.0, "additive": 1.0}

    def __init__(self, form: str = "ito", use_kernels: bool = False):
        if form not in ("ito", "stratonovich"):
            raise ValueError(
                f"unknown Milstein form {form!r}; valid forms: 'ito', "
                "'stratonovich'"
            )
        self.form = form
        self.sde_form = form  # the correction pins the interpretation directly
        self.name = f"Milstein-{form}"
        self.use_kernels = bool(use_kernels)

    def init(self, term, t0, y0, args):
        noise = getattr(term, "noise", "diagonal")
        if noise not in ("none", "diagonal", "additive", "scalar"):
            raise ValueError(
                f"Milstein does not support noise={noise!r}: general "
                "(non-commutative) noise needs full Levy areas; supported "
                "modes: 'diagonal', 'additive', 'scalar', 'none'"
            )
        return y0

    def extract(self, state):
        return state

    def _correction(self, term, t, y, g, h, dW, args):
        """(1/2) (g . grad g) (dW^2 [- h]) as a pytree increment."""

        def g_fn(yy):
            return term.diffusion(t, yy, args)

        _, gdg = jax.jvp(g_fn, (y,), (g,))
        if getattr(term, "noise", "diagonal") == "scalar":
            w2 = dW * dW - h if self.form == "ito" else dW * dW
            return jax.tree_util.tree_map(lambda d: 0.5 * d * w2, gdg)
        if self.form == "ito":
            return jax.tree_util.tree_map(
                lambda d, w: 0.5 * d * (w * w - h), gdg, dW)
        return jax.tree_util.tree_map(lambda d, w: 0.5 * d * (w * w), gdg, dW)

    def _increment(self, term, y, t, h, dW, args):
        f, g = term.evals(t, y, args)
        inc = term.combine(f, g, h, dW, use_kernels=self.use_kernels)
        if g is None:
            return inc
        return tree_add(inc, self._correction(term, t, y, g, h, dW, args))

    def step(self, term, state, t, h, dW, args):
        return tree_add(state, self._increment(term, state, t, h, dW, args))

    def step_with_error(self, term, state, t, h, dW, args):
        """Milstein step with the Ito/Stratonovich correction as the embedded
        error estimate (the difference from the order-1/2 Euler companion)."""
        f, g = term.evals(t, state, args)
        euler = term.combine(f, g, h, dW, use_kernels=self.use_kernels)
        out = tree_add(state, euler)
        if g is None:
            return out, tree_zeros_like(out)
        corr = self._correction(term, t, state, g, h, dW, args)
        return tree_add(out, corr), corr

    def reverse(self, term, state, t, h, dW, args):
        # Subtract the increment re-evaluated at the endpoint (time t + h).
        return tree_sub(state, self._increment(term, state, t + h, h, dW, args))


class SRKAdditive:
    """SRA1 (Roessler 2010): strong order 1.5 for additive noise.

    Two drift stages plus the space-time Levy area ``DH`` (with
    ``DZ = h (DH + DW/2)`` the time-integrated Brownian bridge)::

        k1 = f(t, y)
        y2 = y + (3/4) h k1 + (3/2) g (DH + DW/2)
        y' = y + h (k1/3 + 2 k2/3) + g DW,     k2 = f(t + 3h/4, y2)

    The driver increment is the *pair* ``(dW, dH)`` — solvers advertising
    ``needs_levy_area`` receive it from the Levy-augmented driver queries
    (:meth:`repro.core.brownian.VirtualBrownianTree.levy_area` /
    ``grid_levy_increments``), so bulk realization, adaptive grids, and the
    reversible adjoint's backward re-queries all keep working.  ``reverse``
    replays with the whole pair negated (the scheme is a stage-2 RK in the
    driver, so the negated replay inverts to O(h^2) per step).
    """

    name = "SRA1"
    evals_per_step = 2
    is_reversible = False
    needs_levy_area = True
    # Reads term.diffusion directly — opt out of the prediffused fast path.
    needs_diffusion = True
    sde_form = "ito"  # == stratonovich: additive noise has no correction
    #: documented strong convergence order per supported noise mode
    strong_orders = {"additive": 1.5}

    def __init__(self, noise: str = "additive"):
        if noise != "additive":
            raise ValueError(
                f"srk supports noise='additive' only (t/y-independent "
                f"diffusion), got noise={noise!r}"
            )

    def init(self, term, t0, y0, args):
        noise = getattr(term, "noise", "diagonal")
        if noise != "additive":
            raise ValueError(
                f"SRA1 requires an SDETerm with noise='additive', got "
                f"noise={noise!r} — declare the term additive (diffusion "
                "independent of t and y) or pick another solver"
            )
        return y0

    def extract(self, state):
        return state

    def step(self, term, state, t, h, dW_pair, args):
        dW, dH = dW_pair
        y = state
        k1 = term.drift(t, y, args)
        g = term.diffusion(t, y, args)
        # DZ/h = dH + dW/2 (exact scalar weights; no h division).
        y2 = jax.tree_util.tree_map(
            lambda yi, ki, gi, wi, hi: yi + 0.75 * h * ki
            + 1.5 * gi * (hi + 0.5 * wi),
            y, k1, g, dW, dH)
        k2 = term.drift(t + 0.75 * h, y2, args)
        third = 1.0 / 3.0
        return jax.tree_util.tree_map(
            lambda yi, a, b, gi, wi: yi + h * (third * a + 2.0 * third * b)
            + gi * wi,
            y, k1, k2, g, dW)

    def step_with_error(self, term, state, t, h, dW_pair, args):
        """SRA1 step with its Euler companion as the embedded estimate."""
        dW, _ = dW_pair
        out = self.step(term, state, t, h, dW_pair, args)
        f, g = term.evals(t, state, args)
        y_low = tree_add(state, term.combine(f, g, h, dW))
        return out, tree_sub(out, y_low)

    def reverse(self, term, state, t, h, dW_pair, args):
        return self.step(term, state, t + h, -h, tree_scale(-1.0, dW_pair), args)


def ees25_solver(x: float = 0.1, use_kernels: Optional[bool] = None,
                 use_kernel: Optional[bool] = None) -> LowStorageSolver:
    if x == 0.1:
        return LowStorageSolver(EES25_2N, use_kernels=use_kernels,
                                use_kernel=use_kernel)
    from .williamson import ees25_2n

    return LowStorageSolver(ees25_2n(x), use_kernels=use_kernels,
                            use_kernel=use_kernel)


def ees27_solver(use_kernels: Optional[bool] = None,
                 use_kernel: Optional[bool] = None) -> LowStorageSolver:
    return LowStorageSolver(EES27_2N, use_kernels=use_kernels,
                            use_kernel=use_kernel)
