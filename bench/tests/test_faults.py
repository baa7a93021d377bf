"""A run whose timed path is broken underneath comes out not correct.

Each test skips the look for a chip, plants one fault in the program the
cell drives, runs the rest of the harness at a tiny size on the CPU, and
sees ``correct`` false: a training step that returns its state unchanged,
half of the batch left out (the mean over the rest), and the exchange
between chips left out."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from bench.run import run_cell

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TINY_TRAIN = {"cfg": {"train": {"paths_per_chip": 64}}}


def _cpu():
    import jax

    return jax.devices("cpu")[:1]


def _break_train_step(monkeypatch, wrap):
    import repro.train.trainer as trainer

    real = trainer.make_sde_train_step
    monkeypatch.setattr(trainer, "make_sde_train_step",
                        lambda *a, **kw: wrap(real, *a, **kw))


def test_step_returning_its_state_unchanged_is_caught(monkeypatch, cpu_run):
    def wrap(real, *a, **kw):
        step = real(*a, **kw)

        def frozen(params, opt_state, key):
            _, _, metrics = step(params, opt_state, key)
            return params, opt_state, metrics
        return frozen

    _break_train_step(monkeypatch, wrap)
    res = run_cell("lsde_ou.train", 5, 0.5, False, devices=_cpu(),
                   overrides=TINY_TRAIN)
    assert not res["correct"]
    assert res["checks"]["dparam_gap"]["value"] == pytest.approx(1.0)


def test_half_of_the_batch_left_out_is_caught(monkeypatch, cpu_run):
    def wrap(real, *a, **kw):
        return real(*a, **dict(kw, n_paths=kw["n_paths"] // 2))

    _break_train_step(monkeypatch, wrap)
    res = run_cell("lsde_ou.train", 6, 0.5, False, devices=_cpu(),
                   overrides=TINY_TRAIN)
    assert not res["correct"]
    assert res["checks"]["loss_gap"]["value"] > \
        res["checks"]["loss_gap"]["limit"]


_DP4 = textwrap.dedent("""
    import json, sys
    sys.path[:0] = [{root!r}, {src!r}]
    import jax
    import repro.train.trainer as trainer
    from bench.run import run_cell

    # the training cell data-parallel over four devices, as a mix with a
    # mesh axis runs it
    tiny = {{"cfg": {{"train": {{"paths_per_chip": 16}}}},
            "mix": {{"mesh_axis": "dp"}}}}
    devs = jax.devices()[:4]
    sound = run_cell("lsde_ou.train", 8, 0.5, False, devices=devs,
                     overrides=tiny)
    real = trainer.make_sde_train_step

    def local_only(*a, **kw):
        # each chip's own share of the paths, with no exchange between chips
        n_dev = kw["mesh"].shape[kw["mesh_axis"]]
        return real(*a, **dict(kw, n_paths=kw["n_paths"] // n_dev,
                              mesh=None, mesh_axis=None))

    trainer.make_sde_train_step = local_only
    broken = run_cell("lsde_ou.train", 8, 0.5, False, devices=devs,
                      overrides=tiny)
    print(json.dumps({{"sound": sound["correct"],
                      "broken": broken["correct"],
                      "gap": broken["checks"]["loss_gap"]["value"]}}))
""")


def test_exchange_between_chips_left_out_is_caught(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    code = _DP4.format(root=ROOT, src=os.path.join(ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["sound"] is True
    assert res["broken"] is False
