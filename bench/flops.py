"""Model FLOPs of a training step, counted from a configuration's shapes.

Counts the matrix products of the drift and diffusion evaluations the scheme
makes: ``stages_per_step`` per solver step, on every path, and times 3 for
the forward and the backward pass.  The reversible adjoint's rebuild of the
forward states is recomputation and does not count."""
from __future__ import annotations

from bench import common


def stage_flops(cfg: dict) -> int:
    """FLOPs of one drift and one diffusion evaluation of one path."""
    return common.family(cfg, "reference").stage_flops(cfg["model"])


def train_step_flops(cfg: dict, n_paths: int) -> int:
    s = cfg["solve"]
    return 3 * s["stages_per_step"] * s["n_steps"] * stage_flops(cfg) * n_paths
