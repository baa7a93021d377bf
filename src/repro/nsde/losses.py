"""Distribution-matching losses for NSDE training.

* marginal moment MSE — match per-time mean/std of generated vs target
  trajectories (the OU / GBM experiments).
* wrapped energy score — strictly proper multivariate score with angular
  wrapping on the torus components (the Kuramoto experiment; Gneiting &
  Raftery 2007, eq. as in paper Section 4).
* truncated signature MMD — distance between expected truncated signatures
  (level <= 3) of time-augmented paths (the stochastic-volatility
  experiments; the linear-kernel specialisation of the signature-kernel MMD).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["moment_mse", "wrapped_energy_score", "signature_mmd"]


def moment_mse(gen, target):
    """gen, target: (batch, time[, dim]) — match mean and std trajectories."""
    gm, gs = jnp.mean(gen, axis=0), jnp.std(gen, axis=0)
    tm, ts = jnp.mean(target, axis=0), jnp.std(target, axis=0)
    return jnp.mean((gm - tm) ** 2) + jnp.mean((gs - ts) ** 2)


def _wrap(x):
    return x - 2 * jnp.pi * jnp.round(x / (2 * jnp.pi))


def wrapped_energy_score(samples_th, samples_om, target_th, target_om):
    """Energy score ES = E d(X, y) - 1/2 E d(X, X') with the wrapped-on-theta
    distance d = sum|wrap(dth)| + sum|dom|.  samples: (m, N); target: (N,)."""

    def dist(th_a, om_a, th_b, om_b):
        return jnp.sum(jnp.abs(_wrap(th_a - th_b)), -1) + jnp.sum(jnp.abs(om_a - om_b), -1)

    m = samples_th.shape[0]
    term1 = jnp.mean(dist(samples_th, samples_om, target_th[None], target_om[None]))
    d2 = dist(
        samples_th[:, None], samples_om[:, None], samples_th[None], samples_om[None]
    )
    term2 = jnp.sum(d2) / (2 * m * (m - 1) + 1e-9)
    return term1 - term2


def _outer(a, b):
    """Per-segment outer product, (t, ...) x (t, m) -> (t, ..., m), as a
    broadcast product rather than a ``dot``."""
    return a[..., None] * jnp.expand_dims(b, tuple(range(1, a.ndim)))


def _signature_l3(path):
    """Truncated signature (levels 1..3) of the piecewise-linear path (T, d).

    Level-k terms are iterated integrals; for a piecewise-linear path they
    reduce to iterated sums with the in-segment Chen corrections (1/2 at
    level 2; 1/2, 1/2, 1/6 at level 3).  The sums are reordered so that no
    prefix sum is left (a ``cumsum`` is a ``reduce-window`` on TPU): the
    increment before segment t is ``pre[t] = x_t - x_0``, and the level-2
    term of segment u meets the increment after it, ``post[u] = x_{T-1} -
    x_{u+1}``, so ``sum_t (sum_{u<t} seg2[u]) (x) dx[t] = sum_u seg2[u] (x)
    post[u]``.  The products are broadcasts, not contractions: with no
    ``dot`` in the loss, XLA keeps the batch of paths in the minor dimension.
    """
    dx = path[1:] - path[:-1]  # (T-1, d)
    pre = path[:-1] - path[0]  # increment strictly before each segment
    post = path[-1] - path[1:]  # increment strictly after each segment
    seg2 = _outer(pre + 0.5 * dx, dx)
    seg3 = _outer(seg2, post) + _outer(_outer(0.5 * pre + dx / 6.0, dx), dx)
    s1 = path[-1] - path[0]
    return jnp.concatenate([s1.ravel(), seg2.sum(0).ravel(), seg3.sum(0).ravel()])


def signature_mmd(gen_paths, target_paths, times=None):
    """|| E sig(gen) - E sig(target) ||^2 over time-augmented paths.

    gen/target: (batch, T) or (batch, T, d).
    """
    if gen_paths.ndim == 2:
        gen_paths = gen_paths[..., None]
        target_paths = target_paths[..., None]
    T = gen_paths.shape[1]
    if times is None:
        times = jnp.linspace(0.0, 1.0, T)
    taug = lambda p: jnp.concatenate(
        [jnp.broadcast_to(times[:, None], (T, 1)), p], axis=-1
    )
    sig = jax.vmap(lambda p: _signature_l3(taug(p)))
    return jnp.sum((jnp.mean(sig(gen_paths), 0) - jnp.mean(sig(target_paths), 0)) ** 2)
