#!/usr/bin/env python3
"""Each named scope of the training step's share of the device's busy time,
from a JAX profiler trace (``.xplane.pb``).

    python3 bench/run.py --workload <cell> --seed <n> --seconds 10 \\
        --trace 1 --keep-trace <dir>
    python3 bench/scopes.py <dir>

prints one JSON object: ``scopes`` ({scope: share of busy time, 0-1, for each
of ``SCOPES``}, averaged over devices; empty when no op names a scope),
``covered`` (their sum), ``top_ops`` (the ranked ops with the most device
time, each ``[name, seconds, scope]``) and ``unscoped_ops`` (the same for the
ops no scope names).  ``bench/run.py`` does not call this module: its result
line carries no scope shares.

The window, the busy union and which ops are ranked are those of
``bench/trace_reduce.py``.  What this module adds is each op's HLO
``op_name``:

* read from the HLO modules that the trace's ``/host:metadata`` plane holds,
  by a small reader of the protobuf wire format (``read_op_paths``), matched
  by instruction name and program id: on the TPU that of the ``XLA Modules``
  event that covers the op, on the CPU the op event's ``program_id`` stat;
* an op belongs to the last of ``SCOPES`` in its path, so the transpose of
  ``sde_loss`` is ``sde_loss``'s (``scope_of``);
* a scope's share is the union of its ops' intervals inside the window over
  the device's busy union.  Control flow and async halves are no scope's
  ops, as they are not ranked: a ``while`` event, named by the scope that
  encloses the loop, spans the ops of its body.  The time inside a loop that
  no ranked op covers (its own control, and waits) goes to the scope of the
  innermost loop around it.

Importing this module touches no device.
"""
from __future__ import annotations

import bisect
import functools
import json
import os
import re
import sys
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench.trace_reduce import (_DEVICE_LINES, _NOT_RANKED,  # noqa: E402
                                _TPU_PLANE, SPAN_PREFIX, WINDOW_SPAN,
                                Interval, _stat, clip, find_xplane, gaps,
                                op_name, total, union)

# the named scopes of the training step (src/repro/train/trainer.py,
# src/repro/core/adjoint.py), in the order of the step
SCOPES = ("sde_brownian", "sde_forward", "sde_reverse", "sde_loss",
          "sde_optimizer")
_SCOPE = re.compile(r"(?:^|[/(])(" + "|".join(SCOPES) + r")(?=[/)]|$)")
_CONTROL_FLOW = re.compile(r"^(while|conditional|call)(\.\d+)?$")
_MODULE_LINE = "XLA Modules"
_PROGRAM_ID = re.compile(r"\((\d+)\)$")
_METADATA_PLANE = "/host:metadata"

# a device op: (start_ns, end_ns, event name, HLO op_name or "")
Op = Tuple[int, int, str, str]


@functools.lru_cache(maxsize=None)
def scope_of(op_path: Optional[str]) -> Optional[str]:
    """The last of ``SCOPES`` in an HLO ``op_name`` path, or None:
    ``jit(f)/transpose(jvp(sde_loss))/sde_forward/mul`` -> ``sde_forward``."""
    found = _SCOPE.findall(op_path or "")
    return found[-1] if found else None


@functools.lru_cache(maxsize=None)
def _kind(op_text: str) -> Tuple[str, bool, bool]:
    """(name, ranked, control flow) of a device op's event name."""
    name = op_name(op_text)
    return (name, not _NOT_RANKED.match(name),
            bool(_CONTROL_FLOW.match(name)))


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf: bytes, lo: int = 0, hi: Optional[int] = None):
    """``(field number, value)`` of the protobuf message ``buf[lo:hi]``: an
    int for a varint, a ``(start, end)`` slice for a length-delimited field;
    fixed-width fields are skipped."""
    hi = len(buf) if hi is None else hi
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
            yield key >> 3, v
        elif wire == 2:
            n, i = _varint(buf, i)
            yield key >> 3, (i, i + n)
            i += n
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"unexpected protobuf wire type {wire}")


def _text(buf: bytes, where: Tuple[int, int]) -> str:
    return buf[where[0]:where[1]].decode("utf-8", "replace")


def _hlo_op_paths(buf: bytes, lo: int, hi: int) -> Dict[str, str]:
    """``{instruction name: op_name}`` of one serialized ``xla.HloProto``:
    module (1) > computations (3) > instructions (2) > name (1) and
    metadata (7) > op_name (2)."""
    paths = {}
    for f, module in _fields(buf, lo, hi):
        if f != 1:
            continue
        for g, comp in _fields(buf, *module):
            if g != 3:
                continue
            for h, ins in _fields(buf, *comp):
                if h != 2:
                    continue
                name, path = "", ""
                for k, v in _fields(buf, *ins):
                    if k == 1:
                        name = _text(buf, v)
                    elif k == 7:
                        path = next((_text(buf, w) for j, w in _fields(buf, *v)
                                     if j == 2), "")
                paths[name] = path
    return paths


def read_op_paths(buf: bytes) -> Dict[int, Dict[str, str]]:
    """``{program id: {instruction name: op_name}}`` of the HLO modules held
    by the ``/host:metadata`` plane of a serialized ``XSpace``: planes (1) >
    name (2), event metadata (4, keyed by program id) > stats (5) > bytes
    (6) of the stat whose metadata (5) is named ``Hlo Proto``."""
    out: Dict[int, Dict[str, str]] = {}
    for f, plane in _fields(buf):
        if f != 1:
            continue
        fields = list(_fields(buf, *plane))
        if not any(g == 2 and _text(buf, v) == _METADATA_PLANE
                   for g, v in fields):
            continue
        stat_names = {}
        for g, v in fields:
            if g == 5:
                entry = dict(_fields(buf, *v))
                meta = dict(_fields(buf, *entry[2]))
                stat_names[entry[1]] = _text(buf, meta.get(2, (0, 0)))
        for g, v in fields:
            if g != 4:
                continue
            entry = dict(_fields(buf, *v))
            for h, stat in _fields(buf, *entry[2]):
                stat = dict(_fields(buf, *stat)) if h == 5 else {}
                if stat_names.get(stat.get(1)) == "Hlo Proto" and 6 in stat:
                    out[entry[1]] = _hlo_op_paths(buf, *stat[6])
    return out


def _program_id(module_event) -> Optional[int]:
    """The program id of an ``XLA Modules`` event: the number that ends its
    name (``jit_scanned(11273284105452208888)``)."""
    m = _PROGRAM_ID.search(module_event.name)
    return int(m.group(1)) if m else None


def read_scoped_ops(path: str):
    """(device ops by device, host spans) of one trace file, each device op
    with its HLO ``op_name``: ``{device: [(start_ns, end_ns, name, op_path),
    ...]}``, ``op_path`` "" where the trace holds none.  Host spans:
    ``[(start_ns, end_ns, name), ...]`` of the ``bench.*`` annotations."""
    import warnings

    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        buf = f.read()
    with warnings.catch_warnings():
        # iterating an event's stats warns once per event in this JAX
        warnings.simplefilter("ignore", DeprecationWarning)
        return _read_planes(
            list(ProfileData.from_serialized_xspace(buf).planes),
            read_op_paths(buf))


def _read_planes(planes, op_paths):
    on_tpu = any(_TPU_PLANE.match(p.name) for p in planes)
    ops: Dict[str, List[Op]] = defaultdict(list)
    spans: List[Tuple[int, int, str]] = []
    for plane in planes:
        m = _TPU_PLANE.match(plane.name)
        if m:
            lines = list(plane.lines)
            # the program each op ran in: the module event that covers it
            modules = sorted(
                (int(ev.start_ns), int(ev.start_ns) + int(ev.duration_ns),
                 _program_id(ev))
                for line in lines if line.name == _MODULE_LINE
                for ev in line.events)
            starts = [s for s, _, _ in modules]
            path_of: Dict[Tuple[Optional[int], str], str] = {}
            for line in lines:
                if line.name in _DEVICE_LINES:
                    dev = f"TPU:{m.group(1)}"
                    for ev in line.events:
                        s, name = int(ev.start_ns), ev.name
                        k = bisect.bisect_right(starts, s) - 1
                        pid = modules[k][2] if k >= 0 and s < modules[k][1] \
                            else None
                        path = path_of.get((pid, name))
                        if path is None:
                            path = path_of[pid, name] = op_paths.get(
                                pid, {}).get(op_name(name), "")
                        ops[dev].append((s, s + int(ev.duration_ns), name,
                                         path))
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
                            where = op_paths.get(_stat(ev, "program_id"), {})
                            ops[dev].append((s, e, str(hlo),
                                             where.get(str(hlo), "")))
    return dict(ops), spans


def _scope_ns(evs: Sequence[Op], lo: int, hi: int) -> Dict[str, int]:
    """Nanoseconds of each named scope in ``[lo, hi)`` on one device.

    A scope's time is the union of its ranked ops' intervals, plus the time
    inside a control-flow event of that scope that no ranked op covers (the
    loop's own control and waits), given to the innermost such event."""
    by_scope: Dict[str, List[Interval]] = defaultdict(list)
    ranked: List[Interval] = []
    loops = []
    for s, e, n, path in evs:
        if e <= lo or s >= hi:
            continue
        s, e = max(s, lo), min(e, hi)
        _, is_ranked, is_loop = _kind(n)
        scope = scope_of(path)
        if is_loop:
            loops.append((s, -e, scope))
        elif is_ranked:
            ranked.append((s, e))
            if scope is not None:
                by_scope[scope].append((s, e))
    out = {sc: total(union(ivs)) for sc, ivs in by_scope.items()}
    # sweep the time no ranked op covers; the open loop that started last
    # is the innermost one around t
    loops.sort()
    stack: List[Tuple[int, Optional[str]]] = []
    i = 0
    for g0, g1 in gaps(union(ranked), lo, hi):
        t = g0
        while t < g1:
            while i < len(loops) and loops[i][0] <= t:
                stack.append((-loops[i][1], loops[i][2]))
                i += 1
            while stack and stack[-1][0] <= t:
                stack.pop()
            end = min(g1, loops[i][0] if i < len(loops) else g1)
            if stack:
                end = min(end, stack[-1][0])
                if stack[-1][1] is not None:
                    out[stack[-1][1]] = out.get(stack[-1][1], 0) + end - t
            t = end
    return out


def reduce_scoped(ops: Dict[str, List[Op]],
                  spans: Sequence[Tuple[int, int, str]],
                  top: int = 10) -> Optional[dict]:
    """The scope shares over the window span, and the top ops with their
    scopes (see the module's docstring).  None when the trace holds no
    window span or no device op."""
    windows = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    if not windows or not ops:
        return None
    lo, hi = windows[0]
    share: Dict[str, float] = defaultdict(float)
    op_time: Dict[Tuple[str, Optional[str]], float] = defaultdict(float)
    for evs in ops.values():
        b = total(union(clip([(s, e) for s, e, *_ in evs], lo, hi)))
        for scope, t in _scope_ns(evs, lo, hi).items():
            if b:
                share[scope] += t / b / len(ops)
        for s, e, n, path in evs:
            name, is_ranked, _ = _kind(n)
            if e > lo and s < hi and is_ranked:
                op_time[name, scope_of(path)] += (min(e, hi) - max(s, lo)) \
                    / len(ops)
    ranked = sorted(op_time.items(), key=lambda kv: -kv[1])
    scopes = {sc: share[sc] for sc in SCOPES} if share else {}
    return {
        "scopes": scopes,
        "covered": sum(scopes.values()),
        "top_ops": [[n, t / 1e9, sc] for (n, sc), t in ranked[:top]],
        "unscoped_ops": [[n, t / 1e9] for (n, sc), t in ranked
                         if sc is None][:top],
    }


def reduce_scopes(trace_dir: str, top: int = 10) -> Optional[dict]:
    ops, spans = read_scoped_ops(find_xplane(trace_dir))
    return reduce_scoped(ops, spans, top=top)


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir", help="a directory holding a profiler trace "
                    "recorded around a bench.window span")
    ap.add_argument("--top", type=int, default=10)
    args = ap.parse_args(argv)
    red = reduce_scopes(args.trace_dir, top=args.top)
    if red is None:
        print("no bench.window span or no device op in the trace",
              file=sys.stderr)
        return 1
    print(json.dumps(red), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
