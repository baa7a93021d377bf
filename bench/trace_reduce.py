"""Reduce a JAX profiler trace (``.xplane.pb``) to per-device numbers.

Reads the file with ``jax.profiler.ProfileData`` and nothing else.

* Device operations are the events of the ``XLA Ops`` and ``Async XLA Ops``
  lines of each ``/device:TPU:<n>`` plane, named by their HLO instruction
  (the text before `` = `` of the event's name).  A trace with no such plane
  (one recorded on the CPU) falls back to the host events that carry an
  ``hlo_op`` stat, grouped by their ``device_ordinal``.
* The window is the benchmark's own ``bench.window`` host span.
* Busy time is the union of a device's operation intervals inside the
  window; the idle share is 1 minus busy over the window.
* Collective time is the union of the intervals of operations whose HLO name
  is a collective (all-gather, all-reduce, reduce-scatter, all-to-all,
  collective-permute, and their async start/done halves).
* The ops ranked in the breakdown leave out control flow (``while``,
  ``conditional``, ``call``), whose event spans the ops of its body, and the
  start and done halves of async ops (``copy-start``, ``all-gather-done``),
  which run beside the compute.
* Each idle gap of a device is attributed to the ``bench.*`` host span that
  covers its middle and started last.

Importing this module touches no device.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[int, int]

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
_TPU_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_COLLECTIVES = (r"(all-gather|all-reduce|reduce-scatter|all-to-all"
                r"|collective-permute|collective-broadcast)")
_COLLECTIVE_NAME = re.compile(r"^%?" + _COLLECTIVES)
_COLLECTIVE_OP = re.compile(r"\s" + _COLLECTIVES + r"(-start|-done)?\(")
_DEVICE_LINES = ("XLA Ops", "Async XLA Ops")
# busy, but not ranked: control flow, whose event spans the ops of its body,
# and the halves of async ops, which run beside the compute
_NOT_RANKED = re.compile(r"^((while|conditional|call)|[a-z-]+-(start|done))"
                         r"(\.\d+)?$")


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Sorted, disjoint union of half-open intervals ``(start, end)``."""
    out: List[List[int]] = []
    for s, e in sorted(iv for iv in intervals if iv[1] > iv[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals: Sequence[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def gaps(busy: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    """The idle intervals of ``[lo, hi)`` left by the disjoint ``busy``."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def is_collective(op_text: str) -> bool:
    """Whether an op, by its name or its HLO text, is a collective."""
    return bool(_COLLECTIVE_NAME.match(op_text)
                or _COLLECTIVE_OP.search(op_text))


def op_name(op_text: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return op_text.split(" = ", 1)[0].lstrip("%")


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _stat(event, name):
    for k, v in event.stats:
        if k == name:
            return v
    return None


def read_events(path: str):
    """(device ops by device, host spans) of one trace file.

    Device ops: ``{device: [(start_ns, end_ns, name), ...]}``.  Host spans:
    ``[(start_ns, end_ns, name), ...]`` of the ``bench.*`` annotations."""
    import warnings

    from jax.profiler import ProfileData

    with warnings.catch_warnings():
        # iterating an event's stats warns once per event in this JAX
        warnings.simplefilter("ignore", DeprecationWarning)
        return _read_planes(list(ProfileData.from_file(path).planes))


def _read_planes(planes):
    on_tpu = any(_TPU_PLANE.match(p.name) for p in planes)
    ops: Dict[str, List[Tuple[int, int, str]]] = defaultdict(list)
    spans: List[Tuple[int, int, str]] = []
    for plane in planes:
        m = _TPU_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name in _DEVICE_LINES:
                    dev = f"TPU:{m.group(1)}"
                    for ev in line.events:
                        s = int(ev.start_ns)
                        ops[dev].append((s, s + int(ev.duration_ns), ev.name))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    s = int(ev.start_ns)
                    e = s + int(ev.duration_ns)
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((s, e, ev.name))
                    elif not on_tpu and ev.duration_ns > 0:
                        hlo = _stat(ev, "hlo_op")
                        if hlo is not None:
                            dev = f"CPU:{_stat(ev, 'device_ordinal') or 0}"
                            ops[dev].append((s, e, str(hlo)))
    return dict(ops), spans


def reduce_events(ops: Dict[str, List[Tuple[int, int, str]]],
                  spans: Sequence[Tuple[int, int, str]],
                  top: int = 10) -> Optional[dict]:
    """Per-device busy, idle and collective numbers over the window span.

    Returns None when the trace holds no window span or no device op."""
    windows = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    if not windows or not ops:
        return None
    lo, hi = windows[0]
    width = hi - lo
    inner = [(s, e, n) for s, e, n in spans if n != WINDOW_SPAN]
    devices = {}
    op_time: Dict[str, float] = defaultdict(float)
    idle: List[Tuple[int, int]] = []
    for dev, evs in sorted(ops.items()):
        busy = union(clip([(s, e) for s, e, _ in evs], lo, hi))
        coll = union(clip([(s, e) for s, e, n in evs if is_collective(n)],
                          lo, hi))
        b = total(busy)
        devices[dev] = {"busy_ns": b, "idle_share": 1.0 - b / width,
                        "collective_ns": total(coll),
                        "collective_share": (total(coll) / b) if b else None,
                        "n_ops": len(evs)}
        for s, e, n in evs:
            name = op_name(n)
            if e > lo and s < hi and not _NOT_RANKED.match(name):
                op_time[name] += (min(e, hi) - max(s, lo)) / len(ops)
        idle.extend((g1 - g0, (g0 + g1) // 2) for g0, g1 in gaps(busy, lo, hi))
    n_dev = len(devices)
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(idle, key=lambda x: -x[0])[:top]
    named = []
    for g, mid in idle:
        cover = [(s, n) for s, e, n in inner if s <= mid < e]
        named.append((g, max(cover)[1] if cover else "no bench span"))
    return {
        "window_ns": width,
        "devices": devices,
        "busy_s": sum(d["busy_ns"] for d in devices.values()) / n_dev / 1e9,
        "window_s": width / 1e9,
        "device_ops": [[n, t / 1e9] for n, t in top_ops],
        "idle_gaps": [[n, g / 1e9] for g, n in named],
    }


def reduce_trace(trace_dir: str, top: int = 10) -> Optional[dict]:
    ops, spans = read_events(find_xplane(trace_dir))
    return reduce_events(ops, spans, top=top)
