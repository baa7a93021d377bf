"""Trace reduction: the interval arithmetic on hand-made events, and a small
trace recorded on the CPU."""
import pytest

from bench import trace_reduce as tr


def test_union_clip_gaps_on_hand_made_events():
    ivs = [(10, 20), (15, 30), (40, 50), (50, 55), (70, 70), (60, 65)]
    u = tr.union(ivs)
    assert u == [(10, 30), (40, 55), (60, 65)]
    assert tr.total(u) == 20 + 15 + 5
    assert tr.clip(u, 25, 62) == [(25, 30), (40, 55), (60, 62)]
    assert tr.gaps(u, 0, 80) == [(0, 10), (30, 40), (55, 60), (65, 80)]
    assert tr.gaps([], 5, 9) == [(5, 9)]


def test_reduce_events_idle_collective_and_attribution():
    ops = {"TPU:0": [(100, 200, "fusion.1"), (150, 260, "all-gather.3"),
                     (300, 400, "fusion.2")],
           "TPU:1": [(100, 400, "fusion.1")]}
    spans = [(0, 500, "bench.window"), (0, 250, "bench.train_call"),
             (250, 500, "bench.block")]
    red = tr.reduce_events(ops, spans)
    d0, d1 = red["devices"]["TPU:0"], red["devices"]["TPU:1"]
    assert d0["busy_ns"] == 160 + 100
    assert d0["idle_share"] == pytest.approx(1 - 260 / 500)
    assert d0["collective_ns"] == 110
    assert d0["collective_share"] == pytest.approx(110 / 260)
    assert d1["idle_share"] == pytest.approx(1 - 300 / 500)
    assert d1["collective_share"] == 0
    assert red["busy_s"] == pytest.approx((260 + 300) / 2 / 1e9)
    assert red["window_s"] == pytest.approx(500e-9)
    # gaps of TPU:0 are [0,100) [260,300) [400,500); of TPU:1 [0,100) [400,500)
    lengths = sorted((g for _, g in red["idle_gaps"]), reverse=True)
    assert lengths == pytest.approx([1e-7, 1e-7, 1e-7, 1e-7, 4e-8])
    by_len = {round(g * 1e9): n for n, g in red["idle_gaps"]}
    assert by_len[40] == "bench.block"
    assert dict(red["device_ops"])["fusion.1"] == pytest.approx(
        (100 + 300) / 2 / 1e9)
    assert tr.is_collective("all-reduce-start.2")
    assert not tr.is_collective("fusion.all-gather")


def test_control_flow_and_async_halves_are_busy_but_not_ranked():
    ops = {"TPU:0": [(0, 100, "while.7"), (10, 40, "fusion.1"),
                     (50, 90, "convolution_add_fusion.2"),
                     (5, 95, "copy-start.14"), (95, 100, "copy-done.14"),
                     (20, 60, "all-gather-start.3")]}
    red = tr.reduce_events(ops, [(0, 100, "bench.window")])
    assert red["devices"]["TPU:0"]["idle_share"] == 0.0
    assert [n for n, _ in red["device_ops"]] == ["convolution_add_fusion.2",
                                                 "fusion.1"]


def test_reduce_events_needs_window_and_ops():
    assert tr.reduce_events({}, [(0, 10, "bench.window")]) is None
    assert tr.reduce_events({"TPU:0": [(0, 5, "f")]}, []) is None


def test_reduce_a_trace_recorded_on_the_cpu(tmp_path, cpu_run):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.block"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    red = tr.reduce_trace(str(tmp_path))
    assert red is not None
    assert red["window_s"] > 0
    assert 0 < red["busy_s"] <= red["window_s"]
    for d in red["devices"].values():
        assert 0.0 <= d["idle_share"] < 1.0
        assert d["collective_ns"] == 0
    assert red["device_ops"] and len(red["device_ops"]) <= 10
    assert all(n.startswith("bench.") or n == "no bench span"
               for n, _ in red["idle_gaps"])
