"""Target data of the benchmark's training cells, made in numpy from a seed.

Copies of the generators in ``repro.nsde.data`` and ``repro.nsde.fbm``, kept
here so that the data a cell trains on cannot move when the program does.
"""
from __future__ import annotations

import numpy as np


def ou_paths(rng, batch: int, n_steps: int, T: float, nu: float, mu: float,
             sigma: float) -> np.ndarray:
    """(batch, n_steps + 1) exact Ornstein-Uhlenbeck paths started near 0."""
    h = T / n_steps
    x = np.zeros((batch, n_steps + 1))
    x[:, 0] = rng.standard_normal(batch) * 0.1
    a = np.exp(-nu * h)
    sd = sigma * np.sqrt((1 - a * a) / (2 * nu))
    for n in range(n_steps):
        x[:, n + 1] = mu + (x[:, n] - mu) * a + sd * rng.standard_normal(batch)
    return x


def _fgn_autocov(k: np.ndarray, H: float) -> np.ndarray:
    return 0.5 * (np.abs(k - 1) ** (2 * H) - 2 * np.abs(k) ** (2 * H)
                  + np.abs(k + 1) ** (2 * H))


def fbm_increments(rng, n: int, H: float, T: float, batch: int) -> np.ndarray:
    """(batch, n) fractional Brownian increments (Davies-Harte embedding)."""
    gamma = _fgn_autocov(np.arange(n, dtype=np.float64), H)
    row = np.concatenate([gamma, [0.0], gamma[-1:0:-1]])
    eig = np.maximum(np.fft.fft(row).real, 0.0)
    m = 2 * n
    z = rng.standard_normal((batch, m)) + 1j * rng.standard_normal((batch, m))
    w = np.fft.fft(z * np.sqrt(eig / (2 * m)), axis=1)
    return w[:, :n].real * np.sqrt(2.0) * (T / n) ** H


def rough_vol_paths(rng, batch: int, n_steps: int, T: float, H: float,
                    eta: float = 1.991, v0: float = 0.04, s0: float = 1.0,
                    rho: float = -0.848) -> np.ndarray:
    """(batch, n_steps + 1) rough-Bergomi-style price paths."""
    h = T / n_steps
    t = np.arange(1, n_steps + 1) * h
    wh = np.cumsum(fbm_increments(rng, n_steps, H, T, batch), axis=1)
    v = v0 * np.exp(eta * wh - 0.5 * eta ** 2 * t ** (2 * H))
    z = rng.standard_normal((batch, n_steps))
    g = np.diff(np.concatenate([np.zeros((batch, 1)), wh], axis=1), axis=1)
    g = g / (g.std() + 1e-12)
    dB = (rho * g + np.sqrt(1 - rho ** 2) * z) * np.sqrt(h)
    log_s = np.cumsum(np.sqrt(v) * dB - 0.5 * v * h, axis=1)
    return s0 * np.exp(np.concatenate([np.zeros((batch, 1)), log_s], axis=1))


def make_target(data: dict) -> np.ndarray:
    """The (batch, n_obs) float32 target a configuration's ``data`` names."""
    rng = np.random.default_rng(data["seed"])
    if data["kind"] == "ou":
        x = ou_paths(rng, data["batch"], data["n_obs"], data["T"], data["nu"],
                     data["mu"], data["sigma"])
        return x[:, 1:].astype(np.float32)
    if data["kind"] == "rough_bergomi":
        s = rough_vol_paths(rng, data["batch"], data["n_grid"], data["T"],
                            data["H"])
        stride = data["n_grid"] // data["n_obs"]
        return s[:, ::stride][:, 1:].astype(np.float32)
    raise ValueError(f"unknown target data kind {data['kind']!r}")
