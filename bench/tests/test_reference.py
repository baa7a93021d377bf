"""The harness end to end on the CPU at a tiny size, the look for a chip
skipped: the timed training entry agrees with the plain reference, and a
traced run reduces its own trace."""
import math

import pytest

from bench import common
from bench.run import run_cell

TINY_TRAIN = {"cfg": {"train": {"paths_per_chip": 64}}}


def _cpu():
    import jax

    return jax.devices("cpu")[:1]


def _assert_sound(res):
    assert res["correct"], res["checks"]
    assert res["failed"] == 0
    assert res["attempted"] > 0
    for name, c in res["checks"].items():
        assert math.isfinite(c["value"]) and c["value"] <= c["limit"], name
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("workload,overrides", [
    ("lsde_ou.train", TINY_TRAIN),
    ("lsde_rvol.train", {"cfg": {"train": {"paths_per_chip": 16}}}),
])
def test_timed_training_entry_agrees_with_the_reference(workload, overrides,
                                                        cpu_run):
    res = run_cell(workload, 2 ** 31 + 7, 1.0, False, devices=_cpu(),
                   overrides=overrides)
    _assert_sound(res)
    m = res["metrics"]
    assert set(m) == {"train_steps_per_s", "train_peak_hbm_mib", "setup_s"}
    assert m["train_steps_per_s"]["value"] > 0
    assert res["attempted"] % 8 == 0
    # a CPU run reports the CPU, never a device name
    assert res["device"]["platform"] == "cpu"


def test_traced_training_run_reports_its_per_layer_metrics(cpu_run,
                                                           cpu_peaks):
    res = run_cell("lsde_ou.train", 3, 1.0, True, devices=_cpu(),
                   overrides=TINY_TRAIN)
    _assert_sound(res)
    assert set(res["metrics"]) == {"device_idle_share.train", "train_mfu"}
    assert 0 <= res["metrics"]["device_idle_share.train"]["value"] < 100
    assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert len(res["breakdown"]["device_ops"]) <= 10


def test_cells_name_files_that_exist():
    spec = common.load_json("BENCHMARK.json")
    for w in spec["workloads"]:
        cell = common.load_cell(w["name"])
        assert cell["end_to_end"] and cell["per_layer"]
        assert "setup_s" in {m["name"] for m in cell["end_to_end"]}
        for m in cell["per_layer"]:
            assert callable(common.metric_reader(m["name"]))
