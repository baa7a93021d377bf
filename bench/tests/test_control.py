"""The control: the program at the matrix product precision one step below
the configuration's, with the reference at the configuration's.
``bench/control.py`` makes these readings on the chip at the cells' own
sizes, where the control fails the cells' limits.  The CPU computes float32
products in full whatever precision is asked, so here, at tiny sizes, the
reference at the lower precision stands in for it: it has to read well
above the program, which passes every limit, and half of the batch left out
has to fail them.  A second test sees the control's run drive the program
at the lower precision and the reference at the configuration's."""
import pytest

from bench import common, control

TINY = {"cfg": {"train": {"paths_per_chip": 64}}}


def _cpu():
    import jax

    return jax.devices("cpu")[:1]


def _fails(row, limits):
    return any(row[k] > v for k, v in limits.items())


@pytest.mark.parametrize("workload,paths", [("lsde_ou.train", 64),
                                            ("lsde_rvol.train", 32)])
def test_training_control_and_half_batch_fail(workload, paths, cpu_run):
    cell = common.load_cell(workload)
    cell["cfg"]["train"]["paths_per_chip"] = paths
    rows = []
    control.train_readings(workload, cell, [2 ** 31 + 3], {2 ** 31 + 3},
                           _cpu(), rows, stand_in=True)
    by_kind = {r["kind"]: r for r in rows}
    assert set(by_kind) == {"program", "control", "half_batch"}
    program, ctl = by_kind["program"], by_kind["control"]
    assert not _fails(program, cell["limits"]), program
    assert max(ctl[k] / max(program[k], 1e-9) for k in cell["limits"]) > 3, \
        (program, ctl)
    assert _fails(by_kind["half_batch"], cell["limits"])


def test_control_run_lowers_the_program_not_the_reference(monkeypatch,
                                                          cpu_run):
    import jax

    from bench.drivers import train as T

    seen = {"setup": [], "reference": []}
    real_setup, real_ref = T.setup, T.reference_run

    def setup(*a, **kw):
        seen["setup"].append(jax.config.jax_default_matmul_precision)
        return real_setup(*a, **kw)

    def reference_run(*a, **kw):
        seen["reference"].append(jax.config.jax_default_matmul_precision)
        return real_ref(*a, **kw)

    monkeypatch.setattr(T, "setup", setup)
    monkeypatch.setattr(T, "reference_run", reference_run)
    cell = common.load_cell("lsde_ou.train")
    cell["cfg"]["train"]["paths_per_chip"] = 64
    seed, rows = 2 ** 31 + 9, []
    control.train_readings("lsde_ou.train", cell, [seed], {seed}, _cpu(),
                           rows, seconds=0.5, overrides=TINY)
    assert seen["setup"] == ["highest", "high"]
    assert set(seen["reference"]) == {"highest"}
    ctl = [r for r in rows if r["kind"] == "control"]
    assert len(ctl) == 1 and ctl[0]["precision"] == "high"
    assert set(cell["limits"]) <= set(ctl[0])
    assert isinstance(ctl[0]["correct"], bool)
