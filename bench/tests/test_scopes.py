"""Scope shares of a trace: the last-name rule, shares and loop time on
hand-made events, the protobuf reader on a hand-encoded ``XSpace``, a
synthetic TPU trace and a scoped trace recorded on the CPU."""
import json

import pytest

from bench import scopes as sc
from bench import trace_reduce as tr


@pytest.mark.parametrize("path,scope", [
    ("jit(scanned)/while/body/closed_call/sde_brownian/jit(_threefry)/add",
     "sde_brownian"),
    ("jit(f)/transpose(jvp(sde_loss))/cumsum", "sde_loss"),
    ("jit(f)/transpose(jvp(vmap(sde_reverse)))/while", "sde_reverse"),
    ("jit(f)/sde_loss/transpose(jvp(sde_forward))/mul", "sde_forward"),
    ("jit(f)/sde_reverse/sde_optimizer/where", "sde_optimizer"),
    ("jit(f)/sde_forward_like/mul", None),
    ("jit(f)/while/body/add", None),
    ("", None),
    (None, None),
])
def test_an_op_belongs_to_the_last_scope_in_its_path(path, scope):
    assert sc.scope_of(path) == scope


def test_scope_shares_on_the_trace_reducers_hand_made_case():
    """The events of ``test_trace_reduce``'s attribution case, named by HLO
    op_name paths: the busy union is the one ``trace_reduce`` reads."""
    ops = {"TPU:0": [(100, 200, "fusion.1"), (150, 260, "all-gather.3"),
                     (300, 400, "fusion.2")],
           "TPU:1": [(100, 400, "fusion.1")]}
    spans = [(0, 500, "bench.window"), (0, 250, "bench.train_call"),
             (250, 500, "bench.block")]
    paths = {"fusion.1": "jit(f)/sde_forward/add", "all-gather.3": "",
             "fusion.2": "jit(f)/transpose(jvp(sde_loss))/mul"}
    named = {d: [(s, e, n, paths[n]) for s, e, n in evs]
             for d, evs in ops.items()}
    red = sc.reduce_scoped(named, spans)
    # TPU:0: forward 100 of 260 busy, loss 100; TPU:1: forward 300 of 300
    assert tr.reduce_events(ops, spans)["busy_s"] == pytest.approx(
        (260 + 300) / 2 / 1e9)
    assert red["scopes"]["sde_forward"] == pytest.approx(
        (100 / 260 + 1.0) / 2)
    assert red["scopes"]["sde_loss"] == pytest.approx(100 / 260 / 2)
    assert red["scopes"]["sde_reverse"] == 0.0
    assert list(red["scopes"]) == list(sc.SCOPES)
    assert red["covered"] == pytest.approx(sum(red["scopes"].values()))
    assert red["top_ops"][0] == ["fusion.1", pytest.approx(200e-9),
                                 "sde_forward"]
    assert red["unscoped_ops"] == [["all-gather.3", pytest.approx(55e-9)]]
    # a program without the scopes names none
    bare = {d: [(s, e, n, "") for s, e, n in evs] for d, evs in ops.items()}
    assert sc.reduce_scoped(bare, spans)["scopes"] == {}


def test_scope_shares_give_loop_control_to_the_loop_and_clip_to_the_window():
    loop = "jit(f)/transpose(jvp(sde_reverse))/while"
    ops = {"TPU:0": [
        (0, 100, "while.7", loop),
        (0, 100, "copy-start.2", "jit(f)/sde_loss/copy"),
        (10, 30, "fusion.1", "jit(f)/transpose(jvp(sde_loss))/mul"),
        (20, 40, "fusion.2", "jit(f)/sde_loss/reduce_sum"),
        (50, 70, "fusion.3", "jit(f)/sde_reverse/sde_forward/add"),
        (90, 130, "fusion.4", "jit(f)/sde_optimizer/sub"),
        (60, 80, "copy.5", "")]}
    shares = sc.reduce_scoped(ops, [(0, 100, "bench.window")])["scopes"]
    # the loss's union [10, 40) counts once; the transpose is the loss's
    assert shares["sde_loss"] == pytest.approx(0.30)
    # the last name wins
    assert shares["sde_forward"] == pytest.approx(0.20)
    # the while swallows nothing: only [0, 10) [40, 50) [80, 90), which no
    # ranked op covers (the async half does not count), is the loop's
    assert shares["sde_reverse"] == pytest.approx(0.30)
    # clipped to the window
    assert shares["sde_optimizer"] == pytest.approx(0.10)
    assert shares["sde_brownian"] == 0.0
    assert sum(shares.values()) <= 1.0


def test_loop_time_goes_to_the_innermost_loop():
    ops = {"TPU:0": [
        (0, 100, "while.1", "jit(f)/while"),
        (20, 60, "while.2", "jit(f)/while/body/sde_forward/while"),
        (30, 40, "fusion.1", "jit(f)/while/body/sde_forward/mul"),
        (70, 80, "fusion.2", "jit(f)/while/body/sde_loss/add"),
        (85, 95, "while.3", "jit(f)/while/body/transpose(sde_reverse)/while"),
        (86, 88, "fusion.3", "jit(f)/while/body/sde_reverse/add")]}
    shares = sc.reduce_scoped(ops, [(0, 100, "bench.window")])["scopes"]
    # the inner forward loop: its op's 10 and its 30 of control
    assert shares["sde_forward"] == pytest.approx(0.40)
    assert shares["sde_loss"] == pytest.approx(0.10)
    assert shares["sde_reverse"] == pytest.approx(0.10)
    # the outer loop names no scope: its 40 of control are no scope's
    assert sum(shares.values()) == pytest.approx(0.60)


def test_reduce_scoped_needs_window_and_ops():
    assert sc.reduce_scoped({}, [(0, 10, "bench.window")]) is None
    assert sc.reduce_scoped({"TPU:0": [(0, 5, "f", "")]}, []) is None


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _msg(*fields):
    """A protobuf message of (field number, int or bytes or str) pairs."""
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += _varint(num << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(num << 3 | 2) + _varint(len(v)) + v
    return out


def test_read_op_paths_decodes_the_metadata_planes_hlo_modules():
    def instruction(name, path):
        return _msg((1, name), (2, "fusion"), (7, _msg((1, "add"), (2, path))),
                    (35, 4))

    hlo = _msg((1, _msg((1, "jit_scanned"), (3, _msg(
        (1, "main"), (2, instruction("fusion.1", "jit(scanned)/sde_loss/x")),
        (2, instruction("copy.2", "")))))))
    metadata_plane = _msg(
        (1, 3), (2, "/host:metadata"),
        (5, _msg((1, 1), (2, _msg((1, 1), (2, "Hlo Proto"))))),
        (4, _msg((1, 42), (2, _msg((1, 42), (2, "jit_scanned(42)"),
                                   (5, _msg((1, 1), (6, hlo))))))))
    other_plane = _msg((1, 1), (2, "/host:CPU"),
                       (3, _msg((1, 7), (2, "python"))))
    space = _msg((1, other_plane), (1, metadata_plane), (4, "host"))
    assert sc.read_op_paths(space) == {
        42: {"fusion.1": "jit(scanned)/sde_loss/x", "copy.2": ""}}
    assert sc.read_op_paths(_msg((1, other_plane))) == {}


def _escaped(b):
    return "".join(f"\\{c:03o}" for c in b)


def test_tpu_ops_are_named_through_the_module_that_covers_them(tmp_path):
    """A TPU trace as the profiler writes it: each op of the ``XLA Ops``
    line is named by the HLO module of the ``XLA Modules`` event that covers
    it; an op no module covers gets no path."""
    from jax.profiler import ProfileData

    def hlo(*instructions):
        comp = _msg((1, "main"), *[(2, _msg((1, n), (2, "fusion"),
                                            (7, _msg((2, p)))))
                                   for n, p in instructions])
        return _escaped(_msg((1, _msg((1, "jit_scanned"), (3, comp)))))

    text = f"""
    planes {{ id: 1 name: "/device:TPU:0"
      lines {{ id: 1 name: "XLA Modules" timestamp_ns: 1000
        events {{ metadata_id: 1 offset_ps: 0 duration_ps: 100000 }}
        events {{ metadata_id: 2 offset_ps: 100000 duration_ps: 50000 }} }}
      lines {{ id: 2 name: "XLA Ops" timestamp_ns: 1000
        events {{ metadata_id: 3 offset_ps: 10000 duration_ps: 40000 }}
        events {{ metadata_id: 4 offset_ps: 50000 duration_ps: 50000 }}
        events {{ metadata_id: 3 offset_ps: 110000 duration_ps: 20000 }}
        events {{ metadata_id: 4 offset_ps: 160000 duration_ps: 10000 }} }}
      event_metadata {{ key: 1 value {{ id: 1 name: "jit_scanned(42)" }} }}
      event_metadata {{ key: 2 value {{ id: 2 name: "jit_other(7)" }} }}
      event_metadata {{ key: 3 value {{ id: 3 name: "fusion.1" }} }}
      event_metadata {{ key: 4 value {{ id: 4 name: "fusion.2" }} }} }}
    planes {{ id: 2 name: "/host:metadata"
      stat_metadata {{ key: 5 value {{ id: 5 name: "Hlo Proto" }} }}
      event_metadata {{ key: 42 value {{ id: 42 name: "jit_scanned(42)"
        stats {{ metadata_id: 5 bytes_value: "{hlo(("fusion.1", "a/sde_loss/x"),
                                               ("fusion.2", "a/sde_forward/y"))}" }} }} }}
      event_metadata {{ key: 7 value {{ id: 7 name: "jit_other(7)"
        stats {{ metadata_id: 5 bytes_value: "{hlo(("fusion.1", "b/sde_reverse/z"))}" }} }} }} }}
    planes {{ id: 3 name: "/host:CPU"
      lines {{ id: 1 name: "python" timestamp_ns: 1000
        events {{ metadata_id: 1 offset_ps: 0 duration_ps: 200000 }} }}
      event_metadata {{ key: 1 value {{ id: 1 name: "bench.window" }} }} }}
    """
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    ops, spans = sc.read_scoped_ops(str(path))
    assert spans == [(1000, 1200, "bench.window")]
    assert [(n, p) for _, _, n, p in ops["TPU:0"]] == [
        ("fusion.1", "a/sde_loss/x"), ("fusion.2", "a/sde_forward/y"),
        ("fusion.1", "b/sde_reverse/z"), ("fusion.2", "")]
    shares = sc.reduce_scoped(ops, spans)["scopes"]
    busy = 40 + 50 + 20 + 10
    assert shares["sde_loss"] == pytest.approx(40 / busy)
    assert shares["sde_forward"] == pytest.approx(50 / busy)
    assert shares["sde_reverse"] == pytest.approx(20 / busy)
    # the trace reducer reads the same file as it did
    red = tr.reduce_trace(str(tmp_path))
    assert red["busy_s"] == pytest.approx(busy * 1e-9)


def test_scopes_of_a_trace_recorded_on_the_cpu(tmp_path, cpu_run, capsys):
    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("sde_forward"):
            y = jnp.tanh(x @ x)
        with jax.named_scope("sde_loss"):
            return jnp.sum(jnp.cumsum(y, axis=0) ** 2)

    g = jax.jit(jax.grad(f))
    x = jnp.ones((128, 128))
    g(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            g(x).block_until_ready()
    jax.profiler.stop_trace()
    ops, _ = sc.read_scoped_ops(tr.find_xplane(str(tmp_path)))
    paths = {p for evs in ops.values() for *_, p in evs}
    assert any(sc.scope_of(p) == "sde_loss" for p in paths)
    assert any(sc.scope_of(p) == "sde_forward" for p in paths)
    assert sc.main([str(tmp_path)]) == 0
    red = json.loads(capsys.readouterr().out.splitlines()[-1])
    shares = red["scopes"]
    assert list(shares) == list(sc.SCOPES)
    assert shares["sde_forward"] > 0 and shares["sde_loss"] > 0
    assert shares["sde_reverse"] == shares["sde_brownian"] == 0.0
    assert 0 < red["covered"] <= 1.0 + 1e-9
    assert {s for _, _, s in red["top_ops"]} <= set(sc.SCOPES) | {None}
