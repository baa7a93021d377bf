"""Counts the benchmark computes without a chip: model FLOPs, the peak
table, the metrics read from a reduced trace."""
import pytest

from bench import common, flops


@pytest.mark.parametrize("config,per_stage,n_paths,step_flops", [
    # drift 32->32->32->32: 3 x 2 x 1024 = 6144; diffusion 1->32->32:
    # 2 x 32 + 2 x 1024 = 2112
    ("lsde_ou", 8256, 4096, 3 * 3 * 32 * 8256 * 4096),
    # drift 8->16->16->8: 2 x (128 + 256 + 128) = 1024; diffusion 1->16->8:
    # 2 x 16 + 2 x 128 = 288
    ("lsde_rvol", 1312, 1024, 3 * 3 * 168 * 1312 * 1024),
])
def test_model_flops_match_the_hand_count(config, per_stage, n_paths,
                                          step_flops):
    cfg = common.load_json("bench", "configs", config + ".json")
    assert flops.stage_flops(cfg) == per_stage
    assert flops.train_step_flops(cfg, n_paths) == step_flops


def test_lsde_ou_step_is_about_ten_gflop():
    cfg = common.load_json("bench", "configs", "lsde_ou.json")
    assert flops.train_step_flops(cfg, 4096) == pytest.approx(9.74e9,
                                                              rel=1e-3)


def test_peak_table_knows_the_v5e_and_refuses_an_unknown_kind():
    v5e = common.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["hbm_bytes"] == 16 * 2 ** 30
    assert "TPU v5e" in v5e["source"]
    with pytest.raises(KeyError, match="not in bench/peaks.json"):
        common.peaks("TPU v9 imaginary")


@pytest.mark.parametrize("name", ["device_idle_share.train", "train_mfu"])
def test_metric_readers_find_nothing_without_a_trace(name):
    assert common.metric_reader(name)({}) is None


def test_train_mfu_and_idle_share_from_a_reduced_trace():
    trace = {"devices": {"TPU:0": {"idle_share": 0.25,
                                   "collective_share": 0.1},
                         "TPU:1": {"idle_share": 0.5,
                                   "collective_share": None}}}
    run = {"trace": trace, "steps_per_s": 10.0, "flops_per_step": 1.97e12,
           "chips": 2, "peaks": {"bf16_flops_per_s": 197e12}}
    assert common.metric_reader("train_mfu")(run) == pytest.approx(5.0)
    assert common.metric_reader("device_idle_share.train")(run) == 50.0
