"""The comparison that decides ``correct``, on the CPU at a small size.

A run is driven end to end, with the harness's look for a chip, its table
of peaks and its kernel check stood in for, and the timed path sound and
then broken underneath (``bench/faults.py``): ``correct`` comes out true
once and false for each fault an MCMC cell can have.  The control, the
reference one precision lower in the program's place, fails the cell's own
limits too.  (On the chip, at the cell's own size, the readings of the
control and of the faults are in ``bench/workloads/<cell>.json``.)
"""
import time

import jax
import pytest

from bench import checks, faults, harness, spec

SMALL = {"covtype_nuts4": {"n": 4096}}
CELLS = sorted(SMALL)
SEED = 2**31 + 99


@pytest.fixture(autouse=True)
def no_chip(monkeypatch):
    cpu = {"platform": "cpu", "kind": "cpu", "count": 1}
    monkeypatch.setattr(harness, "device_info",
                        lambda chips: (jax.devices()[:chips], dict(cpu)))
    monkeypatch.setattr(spec, "peaks", lambda kind: None)
    monkeypatch.setattr(harness, "check_kernels", lambda cell, mcmc: None)


def small_cell(name):
    cell = spec.Cell(name)
    cell.config = dict(cell.config, **SMALL[name])
    cell.traffic = dict(cell.traffic, num_warmup=150, num_samples=150)
    return cell


def run(cell):
    return harness.run_cell(cell, SEED, 0.1, False, t_start=time.time())


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    result = run(small_cell(name))
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert list(result)[-1] == "checks"


# each fault, and the number that has to catch it
CAUGHT_BY = {"frozen_state": "failed_fits", "half_batch": "pe_gap",
             "altered_draw": "pe_gap", "hot_momentum": "virial_z"}


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", sorted(CAUGHT_BY))
def test_fault_is_not_correct(name, fault):
    cell = small_cell(name)
    plant = faults.FAULTS[fault]
    with plant(cell.traffic["num_warmup"] + 3) if fault == "altered_draw" \
            else plant():
        result = run(cell)
    assert not result["correct"]
    row = result["checks"][CAUGHT_BY[fault]]
    if fault == "frozen_state":
        assert row["value"] == result["attempted"]
    else:
        assert float(row["value"]) > row["limit"]


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_the_limits(name):
    cell = small_cell(name)
    keys = harness.seed_keys(SEED)
    model_args, inputs = cell.model.make_data(keys["data"], cell.config)
    fit = harness.Fit(harness.build_mcmc(cell), keys["fits"],
                      model_args).fetch()
    program = checks.gaps(cell.reference, inputs, [fit])
    control = checks.gaps(cell.reference, inputs, [fit], control=True)
    assert checks.judge(program, cell.limits)[0]
    assert not checks.judge(control, cell.limits)[0]


def test_virial_reads_the_temperature():
    """Gaussian draws at the posterior's own scale read near 0 standard
    errors; draws 5 % too wide read far off."""
    import numpy as np
    rng = np.random.default_rng(0)
    z = rng.normal(size=(4, 400, 20))
    assert checks.virial_z(z, z) < 3
    wide = 1.05 * z
    assert checks.virial_z(wide, wide) > 10
