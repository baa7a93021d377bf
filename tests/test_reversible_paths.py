"""The two routes of a reversible batch: path by path, or as one batch.

``sdeint`` solves a fixed-grid batch under the reversible adjoint path by
path (``jax.vmap`` outside the custom vjp, one running sum of the args'
cotangent per path) or, where that per-path sum would outgrow
``_PER_PATH_CARRY_BYTES``, as one batch
(:func:`repro.core.adjoint.solve_reversible_paths`: the vmap inside the
custom vjp, the sum over paths taken in every reverse step's vjp).
Invariants under test, in float64:

* outputs, saves and the guard's ``diverged`` flags are bitwise the same on
  both routes; gradients with respect to ``args`` and ``y0`` agree to 1e-12
  of their largest entry (the sums over paths run in another order);
* under ``make_scanned_step`` the batched route's scanned step equals its
  sequential steps bitwise;
* the route flips where the per-path carry crosses the limit;
* compiled on the CPU, the batched route's reverse loop carries no per-path
  copy of a weight's cotangent, where the per-path route carries one for
  each of the four 32 x 32 weights of the LSDE.
"""
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.sdeint  # noqa: F401  (the module, not the function)
from repro.core import SDETerm, brownian_path, get_solver, sdeint, solve
from repro.core.adjoint import solve_reversible_paths
from repro.core.grid import TimeGrid
from repro.nsde import init_lsde, lsde_readout, lsde_term, moment_mse
from repro.optim import adamw, cosine_schedule
from repro.train.trainer import (init_scan_counters, make_scanned_step,
                                 make_sde_train_step)

SDEINT = sys.modules["repro.core.sdeint"]
D = 3
PARAMS = {
    "w": jnp.array([[-0.6, 0.2, 0.1], [0.3, -0.5, 0.0], [0.1, 0.2, -0.4]]),
    "b": jnp.array([0.1, -0.2, 0.05]),
    "s": jnp.array([0.3, 0.2, 0.4]),
}
Y0 = jnp.array([0.5, -0.3, 0.2])
KEYS = jax.random.split(jax.random.PRNGKey(7), 6)
N_STEPS, SAVE_EVERY = 8, 2


def _term(noise):
    def drift(t, y, p):
        return jnp.tanh(y @ p["w"] + p["b"]) - 0.1 * y

    if noise == "additive":
        def diffusion(t, y, p):
            return p["s"]
    else:
        def diffusion(t, y, p):
            return p["s"] * jnp.cos(y)
    return SDETerm(drift=drift, diffusion=diffusion, noise=noise)


@pytest.fixture
def route(monkeypatch):
    """``route("paths")`` solves every reversible batch as one batch,
    ``route("per_path")`` solves none so."""
    def set_route(name):
        monkeypatch.setattr(SDEINT, "_PER_PATH_CARRY_BYTES",
                            0 if name == "paths" else 1 << 62)
    return set_route


def _solve(solver, noise, args, y0, guard):
    return sdeint(_term(noise), solver, 0.0, 1.0, N_STEPS, y0, None,
                  args=args, adjoint="reversible", save_every=SAVE_EVERY,
                  guard=guard, batch_keys=KEYS)


def _loss(solver, noise, guard):
    def f(args, y0):
        r = _solve(solver, noise, args, y0, guard)
        return jnp.sum(r.ys ** 2) + jnp.sum(jnp.sin(r.y_final)), r
    return f


def _close(a, b, rtol=1e-12):
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        x, y = np.asarray(x), np.asarray(y)
        np.testing.assert_allclose(x, y, rtol=0,
                                   atol=rtol * max(np.abs(y).max(), 1e-300))


@pytest.mark.parametrize("guard", [None, 0.6], ids=["noguard", "guard"])
@pytest.mark.parametrize("noise", ["diagonal", "additive"])
@pytest.mark.parametrize("solver", ["ees25", "ees27", "reversible_heun"])
def test_routes_agree(route, solver, noise, guard):
    f = jax.value_and_grad(_loss(solver, noise, guard), argnums=(0, 1),
                           has_aux=True)
    route("per_path")
    (l0, r0), g0 = f(PARAMS, Y0)
    route("paths")
    (l1, r1), g1 = f(PARAMS, Y0)
    for a, b in ((r0.y_final, r1.y_final), (r0.ys, r1.ys), (l0, l1)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    if guard is None:
        assert r0.diverged is None and r1.diverged is None
    else:
        # a threshold some paths cross: the flags are not all alike
        assert 0 < int(np.sum(r0.diverged)) < len(KEYS)
        assert np.array_equal(np.asarray(r0.diverged), np.asarray(r1.diverged))
    _close(g1, g0)


def test_direct_batch_solve_matches_vmapped_solve():
    solver, term = get_solver("ees25"), _term("diagonal")
    paths = [brownian_path(k, 0.0, 1.0, N_STEPS, shape=(D,)) for k in KEYS]
    dWs = jnp.stack([TimeGrid.from_path(bm).increments() for bm in paths])

    def per_path(args, y0):
        r = jax.vmap(lambda k: solve(
            solver, term, y0, brownian_path(k, 0.0, 1.0, N_STEPS, shape=(D,)),
            args, adjoint="reversible", save_every=SAVE_EVERY))(KEYS)
        return jnp.sum(r.ys ** 3), r

    def batch(args, y0):
        r = solve_reversible_paths(
            solver, term, y0, TimeGrid.uniform(0.0, 1.0, N_STEPS), args, dWs,
            save_every=SAVE_EVERY)
        return jnp.sum(r.ys ** 3), r

    (_, r0), g0 = jax.value_and_grad(per_path, (0, 1), has_aux=True)(PARAMS, Y0)
    (_, r1), g1 = jax.value_and_grad(batch, (0, 1), has_aux=True)(PARAMS, Y0)
    assert np.array_equal(np.asarray(r0.ys), np.asarray(r1.ys))
    assert np.array_equal(np.asarray(r0.y_final), np.asarray(r1.y_final))
    _close(g1, g0)


def test_batch_solve_refuses_a_realized_grid():
    grid = TimeGrid(jnp.linspace(0.0, 1.0, 5), jnp.full((4,), 0.25), None,
                    0.0, 1.0)
    with pytest.raises(ValueError, match="uniform"):
        solve_reversible_paths(get_solver("ees25"), _term("diagonal"), Y0,
                               grid, PARAMS, jnp.zeros((2, 4, D)))


def test_scanned_equals_sequential_on_batched_route(route):
    route("paths")
    opt = adamw(cosine_schedule(1e-2, 2, 16))
    step = make_sde_train_step(
        "ees25", _term("diagonal"), opt, lambda p: Y0 + p["b"],
        lambda p, r: jnp.mean(r.ys ** 2) + jnp.mean(r.y_final),
        t0=0.0, t1=1.0, n_steps=N_STEPS, n_paths=8, save_every=SAVE_EVERY)
    jstep = jax.jit(step)
    key = jax.random.PRNGKey(3)
    p, s, losses = PARAMS, opt.init(PARAMS), []
    for i in range(3):
        p, s, m = jstep(p, s, jax.random.fold_in(key, i))
        losses.append(np.asarray(m["loss"]))
    p2, s2, _, hist = make_scanned_step(step, 3)(
        jax.tree_util.tree_map(jnp.array, PARAMS), opt.init(PARAMS),
        init_scan_counters(), key, jnp.asarray(0))
    for a, b in zip(jax.tree_util.tree_leaves((p, s)),
                    jax.tree_util.tree_leaves((p2, s2))):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert np.array_equal(np.asarray(hist["loss"]), np.stack(losses))


@pytest.mark.parametrize("extra_paths, batched", [(0, False), (1, True)],
                         ids=["at_limit", "one_path_over"])
def test_route_flips_at_the_carry_limit(monkeypatch, extra_paths, batched):
    taken = []
    real = SDEINT.solve_reversible_paths

    def spy(*a, **k):
        taken.append(True)
        return real(*a, **k)

    monkeypatch.setattr(SDEINT, "solve_reversible_paths", spy)
    # 16,388 bytes of cotangent a path: the most paths at or under the
    # limit, then one more
    args = {"w": jnp.zeros((64, 64), jnp.float32), "s": jnp.float32(0.1)}
    args_bytes = 64 * 64 * 4 + 4
    n = SDEINT._PER_PATH_CARRY_BYTES // args_bytes + extra_paths
    term = SDETerm(drift=lambda t, y, p: -y * jnp.sum(p["w"]),
                   diffusion=lambda t, y, p: p["s"] * jnp.ones_like(y))
    keys = jax.random.split(jax.random.PRNGKey(0), n)
    jax.eval_shape(lambda a: sdeint(
        term, "ees25", 0.0, 1.0, 4, jnp.zeros(2, jnp.float32), None, args=a,
        adjoint="reversible", batch_keys=keys).y_final, args)
    assert bool(taken) is batched
    assert (SDEINT._per_path_carry_bytes(args, n)
            > SDEINT._PER_PATH_CARRY_BYTES) is batched


def _lsde_reverse_carries(n_paths):
    """Shapes of the carries of the compiled LSDE step's ``sde_reverse``
    loop that lead with the path count (d_z 32, width 32, 16 steps)."""
    target = jax.random.normal(jax.random.PRNGKey(1), (64, 4), jnp.float32)
    params = init_lsde(jax.random.PRNGKey(0), d_obs=1, d_z=32, width=32)
    opt = adamw(cosine_schedule(1e-2, 2, 16), max_grad_norm=1.0)
    step = make_sde_train_step(
        "ees25", lsde_term(), opt,
        y0_fn=lambda p: jnp.zeros(32, jnp.float32) + p["encoder"]["b"],
        loss_fn_result=lambda p, r: moment_mse(
            lsde_readout(p, r.ys)[..., 0], target),
        t0=0.0, t1=2.0, n_steps=16, n_paths=n_paths, save_every=4)
    hlo = make_scanned_step(step, 2).lower(
        params, opt.init(params), init_scan_counters(),
        jax.random.PRNGKey(2), np.int32(0)).compile().as_text()
    carries = []
    for line in hlo.split("\n"):
        m = re.match(r".*?= \((.*?)\) while\(", line)
        if m and "sde_reverse" in line:
            carries += re.findall(rf"f32\[{n_paths},([\d,]+)\]", m.group(1))
    assert carries, "no sde_reverse loop found"
    return carries


@pytest.mark.parametrize("n_paths, weight_carries", [(256, 4), (1024, 0)],
                         ids=["per_path_route", "batched_route"])
def test_reverse_loop_per_path_weight_carries(n_paths, weight_carries):
    # 4,385 float32 parameters: 4.5 MB a batch of 256 paths, 18.0 MB of 1024
    carries = _lsde_reverse_carries(n_paths)
    assert carries.count("32,32") == weight_carries
    # the state and its cotangent stay per path on both routes
    assert carries.count("32") >= 2
