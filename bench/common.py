"""What every cell of the benchmark shares: finding its parts by name, the
device check, the peak table, the compilation counter, and the checks that
decide ``correct``.  Importing this module touches no device."""
from __future__ import annotations

import contextlib
import importlib
import importlib.util
import json
import math
import os
import sys
from typing import Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def load_json(*rel: str) -> dict:
    with open(os.path.join(ROOT, *rel)) as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    """The workload entry ``name`` of ``BENCHMARK.json`` with its
    configuration, traffic mix and limits loaded from their files."""
    spec = load_json("BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    cell = dict(cells[name])
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cell["cfg"] = load_json(conf["file"])
    cell["mix"] = load_json("bench", "traffic", cell["traffic"] + ".json")
    cell["limits"] = load_json("bench", "limits", name + ".json")
    cell["per_layer"] = [m for m in spec["per_layer"]
                         if name in m.get("workloads", [name])]
    cell["end_to_end"] = [m for m in spec["end_to_end"]
                          if name in m.get("workloads", [name])]
    return cell


def family(cfg: dict, part: str):
    """``bench/<part>/<family>.py`` of the configuration's model family
    (``models``: the system under test, ``reference``: the plain one)."""
    return importlib.import_module(f"bench.{part}.{cfg['model']['family']}")


def metric_reader(name: str):
    """The ``read(run)`` function of ``bench/metrics/<name>.py``."""
    path = os.path.join(BENCH_DIR, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str) -> dict:
    table = load_json("bench", "peaks.json")
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in bench/peaks.json"
                       f" ({sorted(table)}); add its published peaks")
    return table[device_kind]


def span(on: bool, name: str):
    """A ``bench.*`` host span in the profiler's trace, when tracing."""
    if not on:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


def check_devices(chips: int):
    """The first ``chips`` TPU devices; exits when there are fewer."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU (jax.devices()[0] is "
                         f"{devs[0].platform!r}); this benchmark runs on the "
                         "chip only")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, found "
                         f"{len(devs)}")
    return devs[:chips]


def memory_peak_bytes(devices) -> Optional[int]:
    peaks_ = [(d.memory_stats() or {}).get("peak_bytes_in_use")
              for d in devices]
    peaks_ = [p for p in peaks_ if p is not None]
    return max(peaks_) if peaks_ else None


class CompileCounter:
    """Counts backend compilations and persistent-cache loads while armed."""

    def __init__(self):
        import jax

        self.armed = False
        self.count = 0

        def on_duration(event, duration, **kw):
            if self.armed and event.endswith("backend_compile_duration"):
                self.count += 1

        def on_event(event, **kw):
            if self.armed and event.endswith("compilation_cache/cache_hits"):
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def rel_gap(a: float, b: float) -> float:
    return abs(a - b) / abs(b) if b else abs(a - b)


def leaf_norm_gaps(prog: Dict[str, float], ref: Dict[str, float],
                   keep: List[str]) -> float:
    """Worst leaf of |‖prog‖ - ‖ref‖| / max(‖ref‖, median leaf ‖ref‖) over
    the leaves ``keep``."""
    vals = sorted(ref[k] for k in keep)
    med = vals[len(vals) // 2]
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keep)


def leaf_norms(tree) -> Dict[str, float]:
    """``{path: float64 norm}`` of every leaf of a pytree."""
    import jax
    import numpy as np

    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): float(np.linalg.norm(
        np.asarray(x, np.float64))) for p, x in flat}


def checks_block(values: Dict[str, float], limits: Dict[str, float]) -> dict:
    """``{name: {"value", "limit"}}`` and whether every value is within."""
    block = {k: {"value": values[k], "limit": limits[k]} for k in limits}
    ok = all(math.isfinite(v["value"]) and v["value"] <= v["limit"]
             for v in block.values())
    return block, ok
