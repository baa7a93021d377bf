"""Distribution-layer tests in a subprocess with 8 fake XLA devices.

Run in a child process because the host device count must stay 1 for every
other test (jax locks device count on first init).
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

# Every case spawns a fresh interpreter with 8 fake XLA devices — tens of
# seconds of jax re-init each; slow lane only.
pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(code: str) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_mesh_and_param_shardings():
    out = run_py("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import param_pspecs, batch_pspecs
        from repro.configs import get_arch
        from repro.models import init_params

        mesh = jax.make_mesh((2, 4), ("data", "model"))
        cfg = get_arch("olmo-1b")
        ap = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
        specs = param_pspecs(mesh, ap)
        flat = jax.tree_util.tree_flatten_with_path(specs)[0]
        d = {"/".join(str(p) for p, _ in [(k.key, None) for k in path]): spec
             for path, spec in flat}
        # embed vocab-sharded; layer wq col-sharded with leading layer axis
        assert tuple(specs["embed"]) == ("model", None), specs["embed"]
        wq = specs["layers"]["attn"]["wq"]
        assert tuple(wq) == (None, None, "model"), wq
        print("OK")
    """)
    assert "OK" in out


def test_sharded_train_step_runs_and_matches_single_device():
    """Real (small) train step executed on an 8-device mesh: loss equals the
    unsharded single-device loss (SPMD correctness)."""
    out = run_py("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_arch
        from repro.models import init_params, loss_fn, ModelOptions, ShardingPolicy
        from repro.launch.mesh import param_pspecs, shardings_for

        cfg = get_arch("qwen3-1.7b").smoke()
        params = init_params(cfg, jax.random.PRNGKey(0))
        batch = {
            "tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab),
            "labels": jax.random.randint(jax.random.PRNGKey(2), (8, 32), 0, cfg.vocab),
        }
        l_single = float(loss_fn(cfg, params, batch))

        mesh = jax.make_mesh((2, 4), ("data", "model"))
        with mesh:
            p_sh = shardings_for(mesh, param_pspecs(mesh, params))
            b_sh = {k: NamedSharding(mesh, P(("data",), None)) for k in batch}
            params_s = jax.device_put(params, p_sh)
            batch_s = jax.device_put(batch, b_sh)
            opts = ModelOptions(shard=ShardingPolicy(batch_axes=("data",), model_axis="model"))
            f = jax.jit(lambda p, b: loss_fn(cfg, p, b, opts),
                        in_shardings=(p_sh, b_sh))
            l_sharded = float(f(params_s, batch_s))
        assert abs(l_single - l_sharded) < 2e-2, (l_single, l_sharded)
        print("OK", l_single, l_sharded)
    """)
    assert "OK" in out


def test_elastic_checkpoint_reshard():
    """Save on a (2,)-mesh, restore onto a (4,)-mesh (elastic recovery)."""
    out = run_py("""
        import os, tempfile
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.train.checkpoint import restore_checkpoint, save_checkpoint

        tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
        mesh2 = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("data",))
        tree2 = jax.device_put(tree, {"w": NamedSharding(mesh2, P("data", None))})
        with tempfile.TemporaryDirectory() as d:
            save_checkpoint(d, 1, tree2)
            mesh4 = jax.sharding.Mesh(np.array(jax.devices()[:4]), ("data",))
            sh4 = {"w": NamedSharding(mesh4, P("data", None))}
            got = restore_checkpoint(d, 1, tree, shardings=sh4)
        np.testing.assert_array_equal(np.asarray(got["w"]), np.asarray(tree["w"]))
        assert len(got["w"].sharding.device_set) == 4
        print("OK")
    """)
    assert "OK" in out


def test_sdeint_mesh_fanout_matches_vmap():
    """shard_map Monte-Carlo fan-out over a device axis: same samples as the
    single-device vmap batch (sdeint's key-based batching is placement-free)."""
    out = run_py("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import SDETerm, sdeint

        term = SDETerm(
            drift=lambda t, y, a: a["nu"] * (a["mu"] - y),
            diffusion=lambda t, y, a: a["sigma"] * jnp.ones_like(y),
            noise="diagonal",
        )
        args = {"nu": jnp.float32(0.2), "mu": jnp.float32(0.1),
                "sigma": jnp.float32(2.0)}
        y0 = jnp.ones(4)
        keys = jax.random.split(jax.random.PRNGKey(0), 16)
        r_vmap = sdeint(term, "ees25", 0.0, 1.0, 8, y0, None, args=args,
                        save_every=4, batch_keys=keys)
        mesh = jax.make_mesh((8,), ("data",))
        r_sharded = sdeint(term, "ees25", 0.0, 1.0, 8, y0, None, args=args,
                           save_every=4, batch_keys=keys,
                           mesh=mesh, mesh_axis="data")
        np.testing.assert_allclose(np.asarray(r_vmap.y_final),
                                   np.asarray(r_sharded.y_final), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(r_vmap.ys),
                                   np.asarray(r_sharded.ys), rtol=1e-5)
        # ambient-mesh form: `with jax.set_mesh(mesh):` supplies the mesh
        with jax.set_mesh(mesh):
            r_ambient = sdeint(term, "ees25", 0.0, 1.0, 8, y0, None,
                               args=args, batch_keys=keys, mesh_axis="data")
        np.testing.assert_allclose(np.asarray(r_sharded.y_final),
                                   np.asarray(r_ambient.y_final), rtol=1e-5)
        print("OK")
    """)
    assert "OK" in out


def test_engine_mesh_sharded_serving_bitwise():
    """Serving with mesh-sharded slots (slots = devices x per_device_slots)
    returns bit-identical SampleResults to plain single-device serving, for
    both single-tick and multi-tick dispatch — path keys are placement-
    independent, so sharding is invisible in the samples."""
    out = run_py("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import SDETerm
        from repro.launch.mesh import make_sample_mesh
        from repro.serving import SDESampleConfig, SDESampleEngine

        term = SDETerm(
            drift=lambda t, y, a: -0.5 * y,
            diffusion=lambda t, y, a: 0.2 * jnp.ones_like(y),
            noise="diagonal",
        )

        def serve(cfg):
            eng = SDESampleEngine(term, jnp.ones(4), cfg)
            r1 = eng.submit("ees25", t1=1.0, n_steps=8, n_paths=20, seed=3)
            r2 = eng.submit("ees25", t1=1.0, n_steps=8, n_paths=5, seed=8)
            done = eng.run()
            return done[r1].y_final, done[r2].y_final

        mesh = make_sample_mesh()  # 8 fake devices on one "mc" axis
        plain = serve(SDESampleConfig(slots=8))
        sharded = serve(SDESampleConfig(slots=8, mesh=mesh, mesh_axis="mc"))
        sharded_multi = serve(SDESampleConfig(slots=8, mesh=mesh,
                                              mesh_axis="mc",
                                              ticks_per_dispatch=3))
        for a, b, c in zip(plain, sharded, sharded_multi):
            np.testing.assert_array_equal(a, b)
            np.testing.assert_array_equal(a, c)

        # indivisible slots are rejected up front, not at dispatch
        try:
            SDESampleEngine(term, jnp.ones(4),
                            SDESampleConfig(slots=6, mesh=mesh, mesh_axis="mc"))
        except ValueError as e:
            assert "multiple of mesh axis" in str(e)
        else:
            raise AssertionError("slots=6 on an 8-way axis should raise")
        print("OK")
    """)
    assert "OK" in out


def test_bench_throughput_mesh_ladder_emits_records():
    """With devices > 1 the throughput bench charts the sharded ladder into
    mesh_records (single-device runs keep records unchanged and empty
    mesh_records)."""
    out = run_py("""
        import os, json, tempfile
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import benchmarks.bench_throughput as bt

        path = os.path.join(tempfile.mkdtemp(), "bench.json")
        bt.run(path, batch_sizes=(4, 16), solvers=("ees25",), n_steps=8, dim=4)
        data = json.load(open(path))
        assert data["n_devices"] == 8, data["n_devices"]
        assert len(data["records"]) == 2
        # batch 4 does not divide over 8 devices -> only batch 16 shards
        mesh = data["mesh_records"]
        assert [r["batch_size"] for r in mesh] == [16], mesh
        assert mesh[0]["devices"] == 8
        assert mesh[0]["speedup_vs_single"] is not None
        assert all("speedup_bulk" in r for r in data["records"])
        print("OK")
    """)
    assert "OK" in out


def test_sde_train_step_data_parallel_bitwise():
    """The PR-10 mesh-sharded SDE train step on 8 fake devices: loss,
    gradients (hence params and opt_state after the update) are BITWISE
    equal to the single-device step — per-path gradients are reduced
    replicated in vmap-transpose order, never psum'd per shard — and the
    scanned chunk preserves that equality."""
    out = run_py("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        jax.config.update("jax_enable_x64", True)
        from repro.core import SDETerm
        from repro.launch.mesh import make_train_mesh
        from repro.optim import adamw, cosine_schedule
        from repro.train.trainer import (init_scan_counters, make_scanned_step,
                                         make_sde_train_step)

        term = SDETerm(
            drift=lambda t, y, p: p["nu"] * (p["mu"] - y),
            diffusion=lambda t, y, p: p["sigma"] * jnp.ones_like(y),
            noise="diagonal",
        )
        params = {"nu": jnp.float64(0.5), "mu": jnp.float64(0.0),
                  "sigma": jnp.float64(0.5)}
        opt = adamw(cosine_schedule(1e-3, 2, 64))
        key = jax.random.PRNGKey(0)
        # cross-path loss on purpose: the sharded step gathers the result
        # before the loss, so moment terms are exact
        loss = lambda p, r: (jnp.mean(r.y_final ** 2)
                             + 0.1 * jnp.mean(jnp.mean(r.y_final, 0) ** 2))
        y0 = lambda p: jnp.zeros(4, jnp.float64)
        common = dict(t0=0.0, t1=1.0, n_steps=16, n_paths=16)

        single = make_sde_train_step("ees25", term, opt, y0, loss, **common)
        mesh = make_train_mesh(8)
        dp = make_sde_train_step("ees25", term, opt, y0, loss,
                                 mesh=mesh, mesh_axis="dp", **common)

        eq = lambda a, b: all(
            np.array_equal(np.asarray(x), np.asarray(y)) for x, y in
            zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)))

        pa, sa, ma = jax.jit(single)(params, opt.init(params), key)
        pb, sb, mb = jax.jit(dp)(params, opt.init(params), key)
        assert eq((pa, sa), (pb, sb)), "dp step != single-device step"
        assert np.array_equal(np.asarray(ma["loss"]), np.asarray(mb["loss"]))

        # scanned K=4 chunk of the dp step == 4 sequential single steps
        js = jax.jit(single)
        p, s = params, opt.init(params)
        for i in range(4):
            p, s, _ = js(p, s, jax.random.fold_in(key, i))
        sc = make_scanned_step(dp, 4)
        p2, s2, _, _ = sc(jax.tree_util.tree_map(jnp.array, params),
                          opt.init(params), init_scan_counters(), key,
                          jnp.asarray(0))
        assert eq((p, s), (p2, s2)), "scanned dp chunk != sequential single"
        print("OK")
    """)
    assert "OK" in out


def test_compressed_gradient_allreduce():
    """int8-quantised all-reduce with error feedback under shard_map."""
    out = run_py("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from repro.optim.compression import compressed_psum_with_feedback

        mesh = jax.make_mesh((8,), ("data",))
        x = jax.random.normal(jax.random.PRNGKey(0), (8, 128))
        err0 = jnp.zeros((8, 128))
        out, err = compressed_psum_with_feedback(mesh, "data", x, err0)
        want = jnp.broadcast_to(x.sum(0, keepdims=True), x.shape)
        rel = float(jnp.max(jnp.abs(out - want)) / (jnp.max(jnp.abs(want)) + 1e-9))
        assert rel < 0.05, rel  # int8 quantisation error bound
        # error feedback accumulates the residual for the next round
        out2, err2 = compressed_psum_with_feedback(mesh, "data", x, err)
        rel2 = float(jnp.max(jnp.abs(out2 + err2.sum(0) - want - err.sum(0))))
        print("OK", rel)
    """)
    assert "OK" in out
