"""Plain reference for the Neural Langevin SDE cells.

Straightforward ``jax.numpy`` in float32, written from the model's equations
and imports nothing of the program:

* model: z0 = b_enc; dz = g(z) dt + f(t) o dW with diagonal noise, where g is
  an MLP [d_z, width, width, d_z] and f(t) = softplus(MLP [1, width, d_z]) *
  0.5 + 0.05, both with LipSwish (0.909 * silu) between layers; readout
  x = z W_out + b_out;
* solver: EES(2,5; x = 1/10) in its Butcher form (a21 = 1/3, a31 = -5/48,
  a32 = 15/16, b = (1/10, 1/2, 2/5)), driven by (h, dW);
* gradient: the reversible adjoint -- the forward pass keeps only the final
  state and the saved outputs; the backward pass rebuilds each earlier state
  by one step with (-h, -dW) and takes the step's vector-Jacobian product
  there;
* Brownian increments: dW[n] of path i under step key k is
  sqrt(h) * normal(fold_in(fold_in(k, i), n), (d_z,)), float32;
* AdamW with global-norm clipping and a warm-up-then-cosine learning rate.

Every matrix product goes through :func:`dot`.  ``precision="highest"`` is
full float32; ``"high"`` is the three-pass bfloat16 product (each operand
split into a bfloat16 head and tail, the tail-by-tail product dropped), the
precision one step below the configuration's.  The CPU computes every
float32 product in full whatever the precision asked, so there this
reference at ``"high"`` stands in for the control; on the chip the control
is the program itself at ``"high"`` (``bench/control.py``).
"""
from __future__ import annotations

import math
from typing import Dict, List

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST

# EES(2,5; 1/10), Butcher form.
A = ((), (1.0 / 3.0,), (-5.0 / 48.0, 15.0 / 16.0))
B = (1.0 / 10.0, 1.0 / 2.0, 2.0 / 5.0)
C = (0.0, 1.0 / 3.0, 5.0 / 6.0)


def _split_bf16(x):
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    lo = (x - hi).astype(jnp.bfloat16).astype(jnp.float32)
    return hi, lo


def dot(a, b, precision: str):
    """``a @ b`` in float32 (``"highest"``) or in three bfloat16 passes
    (``"high"``)."""
    if precision == "highest":
        return jnp.matmul(a, b, precision=HIGHEST)
    if precision == "high":
        ah, al = _split_bf16(a)
        bh, bl = _split_bf16(b)
        mm = lambda x, y: jnp.matmul(x, y, precision=HIGHEST)  # noqa: E731
        return mm(ah, bh) + (mm(ah, bl) + mm(al, bh))
    raise ValueError(f"unknown precision {precision!r}")


# -- weights ------------------------------------------------------------------

def _linear(key, d_in: int, d_out: int):
    k1, _ = jax.random.split(key)
    w = jax.random.normal(k1, (d_in, d_out)) / math.sqrt(d_in)
    return {"w": w.astype(jnp.float32), "b": jnp.zeros((d_out,), jnp.float32)}


def _mlp(key, sizes):
    keys = jax.random.split(key, len(sizes) - 1)
    return [_linear(k, a, b) for k, a, b in zip(keys, sizes[:-1], sizes[1:])]


def init_params(key, d_obs: int, d_z: int, width: int):
    """Random weights from ``key``: a normal(0, 1/fan_in) matrix and a zero
    bias per layer, the key split as encoder, drift, diffusion, readout."""
    ks = jax.random.split(key, 4)
    return {"encoder": _linear(ks[0], d_obs, d_z),
            "drift": _mlp(ks[1], [d_z, width, width, d_z]),
            "diff": _mlp(ks[2], [1, width, d_z]),
            "readout": _linear(ks[3], d_z, d_obs)}


# -- model --------------------------------------------------------------------

def _mlp_apply(layers, x, precision):
    for i, layer in enumerate(layers):
        x = dot(x, layer["w"], precision) + layer["b"]
        if i < len(layers) - 1:
            x = 0.909 * x * jax.nn.sigmoid(x)
    return x


def drift(p, z, precision):
    return _mlp_apply(p["drift"], z, precision)


def diffusion(p, t, precision):
    """f(t), shape (d_z,): the noise does not depend on the state."""
    out = _mlp_apply(p["diff"], jnp.reshape(t, (1, 1)), precision)[0]
    return jax.nn.softplus(out) * 0.5 + 0.05


def ees_step(p, y, t, h, dw, precision):
    """One EES(2,5) step of the batch ``y`` (n_paths, d_z) over (h, dW)."""
    ks: List = []
    for i in range(3):
        yi = y
        for j, a in enumerate(A[i]):
            yi = yi + a * ks[j]
        k = drift(p, yi, precision) * h + diffusion(p, t + C[i] * h,
                                                     precision) * dw
        ks.append(k)
    out = y
    for b, k in zip(B, ks):
        out = out + b * k
    return out


def brownian(keys, n_steps: int, d_z: int, h: float):
    """(n_steps, n_paths, d_z) increments of the paths whose keys are
    ``keys`` (n_paths, 2)."""
    scale = jnp.sqrt(jnp.asarray(h, jnp.float32))

    def one_path(k):
        return jax.vmap(lambda n: scale * jax.random.normal(
            jax.random.fold_in(k, n), (d_z,), jnp.float32))(
                jnp.arange(n_steps))

    return jnp.swapaxes(jax.vmap(one_path)(keys), 0, 1)


def path_keys(key, n_paths: int):
    return jax.vmap(lambda i: jax.random.fold_in(key, i))(jnp.arange(n_paths))


def readout(p, z, precision):
    return dot(z, p["readout"]["w"], precision) + p["readout"]["b"]


# -- losses -------------------------------------------------------------------

def moment_mse(gen, target):
    gm, gs = jnp.mean(gen, axis=0), jnp.std(gen, axis=0)
    tm, ts = jnp.mean(target, axis=0), jnp.std(target, axis=0)
    return jnp.mean((gm - tm) ** 2) + jnp.mean((gs - ts) ** 2)


def _signature3(path, precision):
    """Levels 1-3 of the signature of the piecewise-linear path (T, d)."""
    dx = path[1:] - path[:-1]
    d = dx.shape[1]
    s1 = jnp.sum(dx, axis=0)
    pre = jnp.cumsum(dx, axis=0) - dx
    seg2 = pre[:, :, None] * dx[:, None, :] + 0.5 * dx[:, :, None] * dx[:, None, :]
    s2 = jnp.sum(seg2, axis=0)
    pre2 = jnp.cumsum(seg2, axis=0) - seg2
    # sum_t pre2[t, i, j] dx[t, k], as a matrix product over t
    s3 = jnp.reshape(dot(jnp.reshape(pre2, (-1, d * d)).T, dx, precision),
                     (d, d, d))
    s3 = s3 + jnp.sum(0.5 * pre[:, :, None, None] * dx[:, None, :, None]
                      * dx[:, None, None, :]
                      + dx[:, :, None, None] * dx[:, None, :, None]
                      * dx[:, None, None, :] / 6.0, axis=0)
    return jnp.concatenate([s1.ravel(), s2.ravel(), s3.ravel()])


def signature_mmd(gen, target, precision):
    """|| E sig(gen) - E sig(target) ||^2 over time-augmented 1-d paths
    (batch, T), time running over [0, 1]."""
    T = gen.shape[1]
    times = jnp.linspace(0.0, 1.0, T)

    def sig(x):
        path = jnp.stack([times, x], axis=-1)
        return _signature3(path, precision)

    eg = jnp.mean(jax.vmap(sig)(gen), axis=0)
    et = jnp.mean(jax.vmap(sig)(target), axis=0)
    return jnp.sum((eg - et) ** 2)


def loss_of_saves(cfg, p, ys, target, precision):
    """The configuration's loss on the saved states ys (n_saves, n, d_z)."""
    gen = readout(p, ys, precision)[..., 0].T  # (n_paths, n_saves)
    loss = cfg["loss"]
    if loss["kind"] == "moment_mse":
        return moment_mse(gen, target)
    if loss["kind"] == "signature_mmd":
        return signature_mmd(loss["shift"] + loss["scale"] * gen, target,
                             precision)
    raise ValueError(f"unknown loss {loss['kind']!r}")


# -- gradient by the reversible adjoint ---------------------------------------

def loss_and_grad(cfg, p, keys, target, precision):
    """Loss and parameter gradient of one Monte-Carlo batch (keys (n, 2))."""
    solve = cfg["solve"]
    n_steps, save_every = solve["n_steps"], solve["save_every"]
    t0, t1 = solve["t0"], solve["t1"]
    h = (t1 - t0) / n_steps
    d_z = cfg["model"]["d_z"]
    n_paths = keys.shape[0]
    dws = brownian(keys, n_steps, d_z, h)
    ts = t0 + jnp.arange(n_steps + 1, dtype=jnp.int32) * h
    y0 = jnp.broadcast_to(p["encoder"]["b"], (n_paths, d_z))

    def fwd(y, n):
        y = ees_step(p, y, ts[n], h, dws[n], precision)
        return y, y

    y_final, traj = jax.lax.scan(fwd, y0, jnp.arange(n_steps))
    ys = traj[save_every - 1::save_every]
    loss, vjp_loss = jax.vjp(
        lambda q, s: loss_of_saves(cfg, q, s, target, precision), p, ys)
    g_direct, ct_ys = vjp_loss(jnp.ones((), loss.dtype))

    def bwd(carry, n):
        y, ct, g = carry
        prev = ees_step(p, y, ts[n + 1], -h, -dws[n], precision)
        is_save = (n + 1) % save_every == 0
        idx = jnp.clip((n + 1) // save_every - 1, 0, ys.shape[0] - 1)
        ct = ct + ct_ys[idx] * is_save.astype(ct.dtype)
        _, vjp = jax.vjp(
            lambda q, s: ees_step(q, s, ts[n], h, dws[n], precision), p, prev)
        g_inc, ct_prev = vjp(ct)
        g = jax.tree_util.tree_map(jnp.add, g, g_inc)
        return (prev, ct_prev, g), None

    zeros = jax.tree_util.tree_map(jnp.zeros_like, p)
    (_, ct_y0, g_adj), _ = jax.lax.scan(
        bwd, (y_final, jnp.zeros_like(y_final), zeros),
        jnp.arange(n_steps - 1, -1, -1))
    g = jax.tree_util.tree_map(jnp.add, g_direct, g_adj)
    g["encoder"]["b"] = g["encoder"]["b"] + jnp.sum(ct_y0, axis=0)
    return loss, g


# -- optimizer ----------------------------------------------------------------

def learning_rate(opt, step):
    step = jnp.asarray(step, jnp.float32)
    peak, warmup, total = opt["lr"], opt["warmup"], opt["total"]
    warm = peak * step / max(warmup, 1)
    frac = jnp.clip((step - warmup) / max(total - warmup, 1), 0.0, 1.0)
    cos = peak * (opt["floor"] + (1 - opt["floor"]) * 0.5
                  * (1 + jnp.cos(jnp.pi * frac)))
    return jnp.where(step < warmup, warm, cos)


def adamw_update(opt, g, state, p):
    """One AdamW update; returns (params, state, pre-clip global norm)."""
    leaves = jax.tree_util.tree_leaves(g)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in leaves))
    scale = jnp.minimum(1.0, opt["clip"] / (gnorm + 1e-9))
    g = jax.tree_util.tree_map(lambda x: x * scale, g)
    step = state["step"] + 1
    lr = learning_rate(opt, step)
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
    c1 = 1.0 - b1 ** step.astype(jnp.float32)
    c2 = 1.0 - b2 ** step.astype(jnp.float32)
    mu = jax.tree_util.tree_map(lambda m, x: b1 * m + (1 - b1) * x,
                                state["mu"], g)
    nu = jax.tree_util.tree_map(lambda v, x: b2 * v + (1 - b2) * x * x,
                                state["nu"], g)
    p = jax.tree_util.tree_map(
        lambda q, m, v: q - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps)),
        p, mu, nu)
    return p, {"step": step, "mu": mu, "nu": nu}, gnorm


def train(cfg, p, target, key, step0: int, n_opt_steps: int, n_paths: int,
          precision: str) -> Dict:
    """``n_opt_steps`` optimizer steps from fresh AdamW state; step ``s``
    draws its paths from ``fold_in(key, s)``.  Returns the losses, the
    pre-clip gradient norms, the first gradient, the final first moment and
    the final parameters."""
    opt = cfg["optimizer"]
    zeros = jax.tree_util.tree_map(jnp.zeros_like, p)
    state = {"step": jnp.zeros((), jnp.int32), "mu": zeros, "nu": zeros}

    def one(carry, s):
        q, st = carry
        keys = path_keys(jax.random.fold_in(key, s), n_paths)
        loss, g = loss_and_grad(cfg, q, keys, target, precision)
        q, st, gnorm = adamw_update(opt, g, st, q)
        return (q, st), (loss, gnorm, g)

    steps = step0 + jnp.arange(n_opt_steps, dtype=jnp.int32)
    (p, state), (losses, gnorms, grads) = jax.lax.scan(one, (p, state), steps)
    first = jax.tree_util.tree_map(lambda x: x[0], grads)
    return {"losses": losses, "gnorms": gnorms, "grad_first": first,
            "mu": state["mu"], "params": p}


# -- work ---------------------------------------------------------------------

def mlp_flops(sizes) -> int:
    """Multiply-adds of an MLP's matrix products, counted as 2 FLOPs each."""
    return sum(2 * a * b for a, b in zip(sizes[:-1], sizes[1:]))


def stage_flops(model: dict) -> int:
    """FLOPs of one drift and one diffusion evaluation for one path."""
    d_z, w = model["d_z"], model["width"]
    return mlp_flops([d_z, w, w, d_z]) + mlp_flops([1, w, d_z])
