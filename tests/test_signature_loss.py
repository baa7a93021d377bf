"""The truncated signature of ``signature_mmd`` and its TPU lowering.

``_signature_l3`` computes levels 1..3 of a piecewise-linear path's
signature without a prefix sum.  These tests hold it to three things that
do not depend on how it is computed: a float64 oracle in the plain
``cumsum`` form, Chen's identity on two concatenated paths, and the float64
gradient of ``signature_mmd``.  The last tests pin what the form is for: the
gradient of the loss lowers for TPU with no ``reduce_window`` (the op a
``cumsum`` becomes there) and no ``dot_general``, checked without a chip.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.nsde.losses import _signature_l3, signature_mmd

TS = (2, 3, 6, 17)
DS = (1, 2, 3)


def _oracle_sig(path):
    """Levels 1..3 as iterated sums with prefix sums (the plain form)."""
    dx = jnp.diff(path, axis=0)
    s1 = jnp.sum(dx, axis=0)
    pre = jnp.cumsum(dx, axis=0) - dx
    seg2 = (jnp.einsum("ti,tj->tij", pre, dx)
            + 0.5 * jnp.einsum("ti,tj->tij", dx, dx))
    s2 = jnp.sum(seg2, axis=0)
    pre2 = jnp.cumsum(seg2, axis=0) - seg2
    s3 = (jnp.einsum("tij,tk->ijk", pre2, dx)
          + 0.5 * jnp.einsum("ti,tj,tk->ijk", pre, dx, dx)
          + (1.0 / 6.0) * jnp.einsum("ti,tj,tk->ijk", dx, dx, dx))
    return jnp.concatenate([s1.ravel(), s2.ravel(), s3.ravel()])


def _oracle_mmd(gen, target):
    if gen.ndim == 2:
        gen, target = gen[..., None], target[..., None]
    times = jnp.linspace(0.0, 1.0, gen.shape[1], dtype=gen.dtype)

    def sig(p):
        return _oracle_sig(jnp.concatenate(
            [jnp.broadcast_to(times[:, None], (p.shape[0], 1)), p], -1))

    diff = jnp.mean(jax.vmap(sig)(gen), 0) - jnp.mean(jax.vmap(sig)(target), 0)
    return jnp.sum(diff ** 2)


def _path(seed, t, d):
    """A random walk of ``t`` points in ``d`` dimensions, float64."""
    rng = np.random.default_rng(seed)
    steps = rng.standard_normal((t, d)) / np.sqrt(t)
    return np.cumsum(steps, axis=0) + rng.standard_normal(d)


def _levels(sig, d):
    return (sig[:d], sig[d:d + d * d].reshape(d, d),
            sig[d + d * d:].reshape(d, d, d))


def _rel_err(got, want):
    """Worst gap over the largest entry of ``want``."""
    return float(np.max(np.abs(np.asarray(got, np.float64) - want))
                 / np.max(np.abs(want)))


@pytest.mark.parametrize("d", DS)
@pytest.mark.parametrize("t", TS)
def test_float32_matches_float64_cumsum_oracle(t, d):
    path = _path(1000 * t + d, t, d)
    want = np.asarray(jax.jit(_oracle_sig)(jnp.asarray(path, jnp.float64)))
    with jax.enable_x64(False):
        got = jax.jit(_signature_l3)(jnp.asarray(path, jnp.float32))
        assert got.dtype == jnp.float32
    assert got.shape == (d + d * d + d ** 3,)
    # float32 rounding (about 16 ulps) of sums of at most 16 segments
    assert _rel_err(got, want) < 2e-6


@pytest.mark.parametrize("d", DS)
@pytest.mark.parametrize("t", TS)
def test_chen_identity_on_concatenated_paths(t, d):
    """sig(a * b) = sig(a) (x) sig(b), truncated at level 3, where b starts
    where a ends."""
    a = _path(2000 * t + d, t, d)
    b = _path(3000 * t + d, t + 1, d)
    b = b - b[0] + a[-1]
    ab = np.concatenate([a, b[1:]], axis=0)
    a1, a2, a3 = _levels(np.asarray(_signature_l3(jnp.asarray(a))), d)
    b1, b2, b3 = _levels(np.asarray(_signature_l3(jnp.asarray(b))), d)
    c1, c2, c3 = _levels(np.asarray(_signature_l3(jnp.asarray(ab))), d)
    np.testing.assert_allclose(c1, a1 + b1, rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(c2, a2 + np.einsum("i,j->ij", a1, b1) + b2,
                               rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(
        c3, a3 + np.einsum("ij,k->ijk", a2, b1)
        + np.einsum("i,jk->ijk", a1, b2) + b3, rtol=1e-12, atol=1e-12)


@pytest.mark.parametrize("d", DS)
@pytest.mark.parametrize("t", TS)
def test_loss_gradient_matches_float64(t, d):
    rng = np.random.default_rng(4000 * t + d)
    gen = np.stack([_path(s, t, d) for s in rng.integers(1 << 30, size=8)])
    target = np.stack([_path(s, t, d) for s in rng.integers(1 << 30, size=8)])
    want = np.asarray(jax.jit(jax.grad(_oracle_mmd))(jnp.asarray(gen),
                                                     jnp.asarray(target)))
    with jax.enable_x64(False):
        got = jax.jit(jax.grad(signature_mmd))(
            jnp.asarray(gen, jnp.float32), jnp.asarray(target, jnp.float32))
        assert got.dtype == jnp.float32
    assert got.shape == gen.shape
    assert _rel_err(got, want) < 2e-6


def _tpu_lowering(loss) -> str:
    """The TPU lowering of the loss's gradient at the rough-volatility cell's
    shape (1024 paths, 6 saves)."""
    with jax.enable_x64(False):
        gen = jax.ShapeDtypeStruct((1024, 6), jnp.float32)
        target = jnp.ones((1024, 6), jnp.float32)
        return (jax.jit(jax.grad(loss)).trace(gen, target)
                .lower(lowering_platforms=("tpu",)).as_text())


@pytest.mark.parametrize("loss,count", [(signature_mmd, 0), (_oracle_mmd, 4)],
                         ids=["signature_mmd", "cumsum_form"])
def test_tpu_lowering_reduce_window_count(loss, count):
    """No prefix sum is left in the loss: its TPU gradient has no
    ``reduce_window``, where the ``cumsum`` form (two in the forward pass,
    two in their transposes) has four."""
    text = _tpu_lowering(loss)
    assert "multiply" in text  # the signature's products are there
    assert text.count("reduce_window") == count


def test_tpu_lowering_has_no_contraction():
    """The signature's products are broadcasts, so the loss's gradient holds
    no ``dot_general``: around one, XLA lays the small per-path tensors out
    with the time axis in the TPU's lanes."""
    assert "dot_general" not in _tpu_lowering(signature_mmd)
    assert "dot_general" in _tpu_lowering(_oracle_mmd)
