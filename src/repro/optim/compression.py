"""Gradient compression: int8-quantised all-reduce with error feedback.

The distributed-optimisation trick for bandwidth-bound data parallelism:
gradients are quantised to int8 with a per-tensor scale before crossing the
wire (4x fewer bytes than f32, 2x fewer than bf16) and the quantisation
residual is carried to the next step (error feedback), which keeps SGD/Adam
convergence unaffected to first order (Karimireddy et al., 2019).

``compressed_psum_with_feedback`` is the shard_map building block; the wire
format note: on TPU the int8 payload rides an all-to-all + all-gather pair
(reduce-scatter cannot sum int8 without overflow); this module's reference
implementation psums the dequantised values — same numerics, and the byte
accounting for the roofline uses the int8 payload size.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

def quantize_int8(x):
    scale = jnp.max(jnp.abs(x)) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q, scale):
    return q.astype(jnp.float32) * scale


def compressed_psum_with_feedback(mesh, axis: str, x_stacked, err_stacked):
    """Test/reference harness: leading axis of ``x_stacked`` is sharded over
    ``axis``; returns (summed values broadcast back, new error residuals)."""

    def body(v, e):
        val = v + e  # error feedback
        q, scale = quantize_int8(val)
        deq = dequantize_int8(q, scale)
        new_err = val - deq
        out = jax.lax.psum(deq, axis)  # int8 payload on the wire (see module doc)
        return out, new_err

    f = jax.shard_map(body, mesh=mesh, in_specs=(P(axis), P(axis)),
                      out_specs=(P(axis), P(axis)), check_vma=False)
    return f(x_stacked, err_stacked)


def compress_grads_tree(grads, err_tree, mesh=None, axis: str = "data"):
    """Per-leaf int8 quantise-with-feedback for a gradient pytree (to be used
    inside an existing shard_map'd step; psum is implicit under SPMD)."""

    def one(g, e):
        val = g.astype(jnp.float32) + e
        q, scale = quantize_int8(val)
        deq = dequantize_int8(q, scale)
        return deq.astype(g.dtype), val - deq

    pairs = jax.tree_util.tree_map(one, grads, err_tree)
    g2 = jax.tree_util.tree_map(lambda t: t[0], pairs,
                                is_leaf=lambda t: isinstance(t, tuple))
    e2 = jax.tree_util.tree_map(lambda t: t[1], pairs,
                                is_leaf=lambda t: isinstance(t, tuple))
    return g2, e2
