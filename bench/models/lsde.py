"""The system under test for the Neural Langevin SDE configurations.

Builds, from a configuration file's numbers, what the program offers its
users: the weights (``repro.nsde.init_lsde``, one jitted call on the
device) and the scanned training step (``make_sde_train_step`` under
``make_scanned_step``, optionally data-parallel over a
``make_train_mesh``).  The plain reference lives in
``bench/reference/lsde.py``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def init_params(cfg, key):
    from repro.nsde import init_lsde

    m = cfg["model"]
    return jax.jit(lambda k: init_lsde(k, d_obs=m["d_obs"], d_z=m["d_z"],
                                       width=m["width"]))(key)


def y0_of(params):
    return jnp.zeros(params["encoder"]["b"].shape, jnp.float32) \
        + params["encoder"]["b"]


def _loss_of_result(cfg, target):
    from repro.nsde import lsde_readout, moment_mse, signature_mmd

    loss = cfg["loss"]
    if loss["kind"] == "moment_mse":
        def fn(p, r):
            return moment_mse(lsde_readout(p, r.ys)[..., 0], target)
    elif loss["kind"] == "signature_mmd":
        shift, scale = loss["shift"], loss["scale"]

        def fn(p, r):
            gen = lsde_readout(p, r.ys)[..., 0]
            return signature_mmd(shift + scale * gen, target)
    else:
        raise ValueError(f"unknown loss {loss['kind']!r}")
    return fn


def build_train(cfg, target, *, n_paths: int, steps_per_call: int,
                mesh=None, mesh_axis=None):
    """The scanned step ``(params, opt_state, counters, key, step0) ->
    (params, opt_state, counters, hist)`` and the optimizer."""
    from repro.nsde import lsde_term
    from repro.optim import adamw, cosine_schedule
    from repro.train.trainer import make_scanned_step, make_sde_train_step

    o, s = cfg["optimizer"], cfg["solve"]
    opt = adamw(cosine_schedule(o["lr"], o["warmup"], o["total"],
                                floor=o["floor"]),
                b1=o["b1"], b2=o["b2"], eps=o["eps"], max_grad_norm=o["clip"])
    step = make_sde_train_step(
        s["solver"], lsde_term(), opt, y0_fn=y0_of,
        loss_fn_result=_loss_of_result(cfg, jnp.asarray(target)),
        t0=s["t0"], t1=s["t1"], n_steps=s["n_steps"], n_paths=n_paths,
        adjoint=s["adjoint"], save_every=s["save_every"],
        mesh=mesh, mesh_axis=mesh_axis)
    return make_scanned_step(step, steps_per_call), opt
