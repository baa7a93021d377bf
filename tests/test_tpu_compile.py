"""Compile every Pallas kernel of the main path for a described TPU v5e.

Nothing runs: each test lowers a kernel at a real width for one chip of a
``v5e:2x2`` topology that is described, not attached, and asserts that the
TPU compiler accepted it as a Mosaic custom call.  This catches what the
interpret-mode parity tests cannot — block shapes the TPU tiling refuses,
kernels that do not fit fast memory — without a chip.

The topology is described inside a module fixture (never at import time), so
every pytest-xdist worker collects the same tests and only the worker that
runs this file loads the TPU compiler.  JAX's persistent compilation cache
is off around the compiles: an entry compiled for a described chip cannot be
read back without one.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.sde_step import sde_step as K
from repro.kernels.ssd_scan.ssd_scan import ssd_scan
from repro.kernels.williamson2n.williamson2n import williamson2n_2d

CUSTOM_CALL = 'custom_call_target="tpu_custom_call"'
ROWS = 1024  # (1024, 128) f32 rows: a 131072-float state per kernel call
A, B = -0.5, 0.75  # Williamson 2N stage coefficients (any floats compile)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever stops the description
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compiled_text(fn, sharding, *shapes, dtype=jnp.float32) -> str:
    # conftest turns on x64 for the numerics tests; the chip runs without it,
    # and Mosaic refuses the 64-bit grid indices x64 would give the kernels.
    with jax.enable_x64(False):
        args = [jax.ShapeDtypeStruct(s, dtype, sharding=sharding)
                for s in shapes]
        return jax.jit(fn).lower(*args).compile().as_text()


def _rows(n=ROWS):
    return (n, K.LANE)


H = (1, 1)  # the step size operand of every sde_step kernel

# name -> (kernel with static args bound, operand shapes)
SDE_STEP = {
    "increment_diag": (K.increment_diag_2d, [_rows()] * 3 + [H]),
    "increment_general": (K.increment_general_2d,
                          [(ROWS, 32), (ROWS, 32, 128), (ROWS, 128), H]),
    "increment_prediffused": (K.increment_pre_2d, [_rows()] * 2 + [H]),
    "ws_stage_diag": (functools.partial(K.ws_stage_diag_2d, a=A, b=B),
                      [_rows()] * 5 + [H]),
    "ws_stage_diag_bwd": (functools.partial(K.ws_stage_diag_bwd_2d, a=A, b=B),
                          [_rows()] * 4 + [H]),
    "ws_stage_prediffused": (functools.partial(K.ws_stage_pre_2d, a=A, b=B),
                             [_rows()] * 4 + [H]),
    "ws_stage_general": (functools.partial(K.ws_stage_general_2d, a=A, b=B),
                         [(ROWS, 32)] * 3 + [(ROWS, 32, 128), (ROWS, 128), H]),
    "axpy_chain": (functools.partial(K.axpy_chain_2d, coeffs=(0.5, 0.25, 0.25)),
                   [_rows(), (3,) + _rows()]),
    "williamson2n": (functools.partial(williamson2n_2d, a=A, b=B),
                     [_rows()] * 3),
}


@pytest.mark.parametrize("name", sorted(SDE_STEP))
def test_sde_step_kernel_compiles(one_chip, name):
    fn, shapes = SDE_STEP[name]
    assert CUSTOM_CALL in _compiled_text(fn, one_chip, *shapes)


def test_ws_stage_diag_under_vmap_compiles(one_chip):
    """The batched solve vmaps the per-path stage: 16 paths of 1024 rows."""
    stage = functools.partial(K.ws_stage_diag_2d, a=A, b=B)
    batched = jax.vmap(stage, in_axes=(0, 0, 0, 0, 0, None))
    shapes = [(16,) + _rows()] * 5 + [H]
    assert CUSTOM_CALL in _compiled_text(batched, one_chip, *shapes)


@pytest.mark.parametrize("q_heads,kv_heads", [(16, 8), (16, 16)])
def test_flash_attention_compiles(one_chip, q_heads, kv_heads):
    """bf16, sequence 2048, head_dim 128, grouped and plain heads."""
    fn = functools.partial(flash_attention, causal=True)
    text = _compiled_text(fn, one_chip, (1, q_heads, 2048, 128),
                          (1, kv_heads, 2048, 128), (1, kv_heads, 2048, 128),
                          dtype=jnp.bfloat16)
    assert CUSTOM_CALL in text


def test_ssd_scan_compiles(one_chip):
    """mamba2-130m's SSD layer: 24 heads of 64, d_state 128, chunk 128."""
    b, l, h, dh, ds = 2, 2048, 24, 64, 128
    fn = functools.partial(ssd_scan, chunk=128)
    text = _compiled_text(fn, one_chip, (b, l, h, dh), (b, l, h), (h,),
                          (b, l, ds), (b, l, ds))
    assert CUSTOM_CALL in text
