"""Euclidean solver behaviour: step equivalences, reversibility orders,
adjoint gradient agreement, Brownian reconstruction."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    BrownianPath,
    ButcherSolver,
    MCFSolver,
    ReversibleHeun,
    SDETerm,
    brownian_path,
    ees25,
    ees25_solver,
    ees27_solver,
    euler,
    heun,
    midpoint,
    realize_grid,
    rk4,
    solve,
    virtual_brownian_tree,
)

KEY = jax.random.PRNGKey(0)


def nonlinear_ode_term():
    return SDETerm(drift=lambda t, y, a: jnp.sin(y) + 0.3 * y * jnp.cos(t), noise="none")


def nonlinear_sde_term():
    return SDETerm(
        drift=lambda t, y, a: jnp.tanh(a["w"] * y + a["b"]),
        diffusion=lambda t, y, a: 0.2 + 0.1 * jnp.tanh(a["g"] * y),
        noise="diagonal",
    )


ARGS = {"w": jnp.float64(0.5), "b": jnp.float64(-0.2), "g": jnp.float64(0.3)}


class TestStepEquivalences:
    def test_butcher_equals_2n(self):
        """The Williamson 2N recurrence computes the identical RK step."""
        term = nonlinear_sde_term()
        y0 = jnp.array([0.4, -1.1, 0.8])
        dW = jnp.array([0.03, -0.05, 0.02])
        y_butcher = ButcherSolver(ees25).step(term, y0, 0.1, 0.05, dW, ARGS)
        y_2n = ees25_solver().step(term, y0, 0.1, 0.05, dW, ARGS)
        np.testing.assert_allclose(y_butcher, y_2n, rtol=1e-12)

    def test_general_noise_matches_diagonal(self):
        """A diagonal diffusion expressed as a (d, d) matrix gives the same step."""
        gvals = jnp.array([0.2, 0.3, 0.1])
        term_d = SDETerm(
            drift=lambda t, y, a: -y,
            diffusion=lambda t, y, a: gvals * jnp.ones_like(y),
            noise="diagonal",
        )
        term_g = SDETerm(
            drift=lambda t, y, a: -y,
            diffusion=lambda t, y, a: jnp.diag(gvals),
            noise="general",
        )
        y0 = jnp.array([1.0, 2.0, 3.0])
        dW = jnp.array([0.1, -0.2, 0.05])
        s = ees25_solver()
        np.testing.assert_allclose(
            s.step(term_d, y0, 0.0, 0.01, dW, None),
            s.step(term_g, y0, 0.0, 0.01, dW, None),
            rtol=1e-12,
        )


class TestReversibility:
    @pytest.mark.parametrize(
        "solver,expected_order",
        [(ees25_solver(), 6), (ees27_solver(), 8)],
    )
    def test_effective_symmetry_order(self, solver, expected_order):
        """Phi_{-h} o Phi_h = id + O(h^{m+1}): slope of log-error vs log-h."""
        term = nonlinear_ode_term()
        y0 = jnp.array([0.7, -0.4], dtype=jnp.float64)
        hs = np.array([0.1, 0.05, 0.025])
        errs = []
        for h in hs:
            y1 = solver.step(term, y0, 0.0, h, None, None)
            y0b = solver.reverse(term, y1, 0.0, h, None, None)
            errs.append(float(jnp.max(jnp.abs(y0b - y0))))
        slope = np.polyfit(np.log(hs), np.log(np.maximum(errs, 1e-300)), 1)[0]
        assert slope > expected_order - 0.5

    @pytest.mark.parametrize(
        "solver", [ReversibleHeun(), MCFSolver(euler), MCFSolver(midpoint), MCFSolver(heun)]
    )
    def test_exact_algebraic_reversibility(self, solver):
        term = nonlinear_sde_term()
        y0 = jnp.array([0.4, -1.1], dtype=jnp.float64)
        state = solver.init(term, 0.0, y0, ARGS)
        dW = jnp.array([0.07, -0.02])
        s1 = solver.step(term, state, 0.0, 0.1, dW, ARGS)
        s0 = solver.reverse(term, s1, 0.0, 0.1, dW, ARGS)
        for a, b in zip(jax.tree_util.tree_leaves(s0), jax.tree_util.tree_leaves(state)):
            np.testing.assert_allclose(a, b, atol=1e-13)

    def test_multistep_reconstruction_drift_small(self):
        """Reconstructing 256 EES steps backwards stays within tolerance."""
        term = nonlinear_sde_term()
        bm = brownian_path(KEY, 0.0, 1.0, 256, shape=(4,), dtype=jnp.float64)
        solver = ees25_solver()
        y = jnp.ones(4, dtype=jnp.float64)
        ys = [y]
        for n in range(bm.n_steps):
            y = solver.step(term, y, bm.t_of(n), bm.h, bm.increment(n), ARGS)
            ys.append(y)
        yb = y
        for n in range(bm.n_steps - 1, -1, -1):
            yb = solver.reverse(term, yb, bm.t_of(n), bm.h, bm.increment(n), ARGS)
        assert float(jnp.max(jnp.abs(yb - ys[0]))) < 1e-8


class TestAdjoints:
    def _loss(self, adjoint, solver):
        def loss(params, key):
            term = nonlinear_sde_term()
            bm = brownian_path(key, 0.0, 1.0, 128, shape=(8,), dtype=jnp.float64)
            r = solve(
                solver, term, jnp.ones(8, jnp.float64), bm, params,
                adjoint=adjoint, save_every=16,
            )
            return jnp.sum(r.y_final ** 2) + jnp.sum(r.ys ** 2)

        return loss

    def test_full_equals_recursive(self):
        s = ees25_solver()
        gf = jax.grad(self._loss("full", s))(ARGS, KEY)
        gr = jax.grad(self._loss("recursive", s))(ARGS, KEY)
        for k in ARGS:
            np.testing.assert_allclose(gf[k], gr[k], rtol=1e-10)

    def test_reversible_close_to_full_ees(self):
        s = ees25_solver()
        gf = jax.grad(self._loss("full", s))(ARGS, KEY)
        gr = jax.grad(self._loss("reversible", s))(ARGS, KEY)
        for k in ARGS:
            np.testing.assert_allclose(gf[k], gr[k], rtol=1e-6)

    @pytest.mark.parametrize("solver", [ReversibleHeun(), MCFSolver(midpoint)])
    def test_reversible_exact_for_algebraic_solvers(self, solver):
        gf = jax.grad(self._loss("full", solver))(ARGS, KEY)
        gr = jax.grad(self._loss("reversible", solver))(ARGS, KEY)
        for k in ARGS:
            np.testing.assert_allclose(gf[k], gr[k], rtol=1e-9)

    def test_reversible_jits(self):
        s = ees25_solver()
        g1 = jax.grad(self._loss("reversible", s))(ARGS, KEY)
        g2 = jax.jit(jax.grad(self._loss("reversible", s)))(ARGS, KEY)
        for k in ARGS:
            np.testing.assert_allclose(g1[k], g2[k], rtol=1e-12)

    def test_grad_wrt_y0(self):
        term = nonlinear_sde_term()
        bm = brownian_path(KEY, 0.0, 1.0, 64, shape=(4,), dtype=jnp.float64)

        def loss(y0, adjoint):
            r = solve(ees25_solver(), term, y0, bm, ARGS, adjoint=adjoint)
            return jnp.sum(r.y_final ** 2)

        y0 = jnp.array([1.0, 0.5, -0.5, 2.0])
        gf = jax.grad(lambda y: loss(y, "full"))(y0)
        gr = jax.grad(lambda y: loss(y, "reversible"))(y0)
        np.testing.assert_allclose(gf, gr, rtol=1e-6)

    @pytest.mark.parametrize("bulk", [True, False], ids=["bulk", "per-step"])
    @pytest.mark.parametrize("guard", [None, 1e6], ids=["unguarded", "guarded"])
    @pytest.mark.parametrize("saves", ["save_every", "save_at"])
    def test_saved_trajectory_identical_across_adjoints(self, saves, guard,
                                                        bulk):
        """Every adjoint runs the one forward sweep: ``y_final``, ``ys`` and
        ``diverged`` are bitwise equal across full, recursive and
        reversible, on a uniform grid with ``save_every`` and on an
        adaptively realized grid with ``save_at``."""
        term = nonlinear_sde_term()
        y0 = jnp.ones(4)
        if saves == "save_every":
            grid = brownian_path(KEY, 0.0, 1.0, 64, shape=(4,),
                                 dtype=jnp.float64)
            kw = {"save_every": 8}
        else:
            vbt = virtual_brownian_tree(KEY, 0.0, 1.0, shape=(4,),
                                        dtype=jnp.float64)
            grid = realize_grid("ees25", term, y0, vbt, ARGS, rtol=1e-3,
                                max_steps=64).grid
            kw = {"save_at": jnp.linspace(0.0, 1.0, 9)}
        outs = [
            solve(ees25_solver(), term, y0, grid, ARGS, adjoint=a,
                  guard=guard, bulk_increments=bulk, **kw)
            for a in ("full", "recursive", "reversible")
        ]
        for out in outs[1:]:
            np.testing.assert_array_equal(out.y_final, outs[0].y_final)
            np.testing.assert_array_equal(out.ys, outs[0].ys)
            if guard is None:
                assert out.diverged is None
            else:
                np.testing.assert_array_equal(out.diverged, outs[0].diverged)


class TestBrownian:
    def test_increments_deterministic_and_orderfree(self):
        bm = brownian_path(KEY, 0.0, 1.0, 100, shape=(3,))
        a = bm.increment(42)
        b = bm.increment(7)
        a2 = bm.increment(42)
        np.testing.assert_array_equal(a, a2)
        assert not np.allclose(a, b)

    def test_variance_scaling(self):
        bm = brownian_path(KEY, 0.0, 2.0, 50, shape=(20000,))
        inc = bm.increment(3)
        assert float(jnp.var(inc)) == pytest.approx(2.0 / 50, rel=0.1)

    def test_pytree_shapes(self):
        bm = brownian_path(KEY, 0.0, 1.0, 10, shape=((3,), (5,)))
        dw = bm.increment(0)
        assert dw[0].shape == (3,) and dw[1].shape == (5,)

    def test_path_endpoints(self):
        bm = brownian_path(KEY, 0.0, 1.0, 16, shape=())
        w = bm.path()
        assert w.shape == (17,)
        total = sum(float(bm.increment(n)) for n in range(16))
        assert float(w[-1]) == pytest.approx(total, rel=1e-5)
