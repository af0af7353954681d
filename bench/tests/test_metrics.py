"""Operation and byte counts, and the per-layer readers, on known answers."""
import numpy as np
import pytest

from bench import spec
from bench import trace as tm

PEAK = spec.peaks("TPU v5 lite")
MS = 1_000_000


class Fit:
    def __init__(self, steps):
        self.steps = steps
        self.grads = int(sum(np.sum(s) for s in steps.values()))


class Run:
    def __init__(self, cell_name, ops, fits, dropped_at=None):
        from bench.harness import LayerRun
        host = [("bench_fit", 0, 100 * MS, "python3")]
        tr = tm.Trace({"/device:TPU:0": ops}, host, "bench_fit", dropped_at)
        self.__dict__.update(vars(LayerRun(spec.Cell(cell_name), tr, fits,
                                           PEAK)))


def test_work_counts():
    cov = spec.Cell("covtype_nuts4")
    cost = cov.model.glm_call_cost(cov.config, 4)
    assert cost == {"flops": 4 * 581012 * 54 * 4,
                    "bytes": 4 * (581012 * 54 + 581012)}
    assert cov.model.flops_per_grad(cov.config) == 4 * 581012 * 54


def test_glm_roofline_counts_x_once_per_call():
    cov = spec.Cell("covtype_nuts4")
    cost = cov.model.glm_call_cost(cov.config, 4)
    least_ns = cost["bytes"] / PEAK["hbm_bytes_per_s"] * 1e9  # memory-bound
    # two calls, each four times its least time: 25 %
    ops = [("jvp_glm_potential_grad_.1", 0, int(4 * least_ns)),
           ("jvp_glm_potential_grad_.1", 50 * MS, 50 * MS + int(4 * least_ns))]
    run = Run("covtype_nuts4", ops, [Fit({"sample": np.ones((4, 2))})])
    read = spec.Cell("covtype_nuts4").reader("glm_kernel_roofline")
    assert read(run) == pytest.approx(25.0, rel=1e-6)


def test_step_mfu_and_idle_share():
    cov = spec.Cell("covtype_nuts4")
    fit = Fit({"warmup": np.full((4, 10), 5), "sample": np.full((4, 10), 5)})
    run = Run("covtype_nuts4", [("fusion.1", 0, 75 * MS)], [fit])
    flops = 400 * cov.model.flops_per_grad(cov.config)
    assert cov.reader("step_mfu")(run) == pytest.approx(
        100 * flops / (0.1 * PEAK["flops_per_s"]))
    assert cov.reader("device_idle_share")(run) == pytest.approx(25.0)


def test_lockstep_waste():
    cov = spec.Cell("covtype_nuts4")
    # two chains, one draw a phase: the deeper trees take 4 and 8 steps,
    # so 2 * (4 + 8) = 24 ran in lockstep and 18 were used
    steps = {"warmup": np.array([[4], [2]]), "sample": np.array([[8], [4]])}
    run = Run("covtype_nuts4", [], [Fit(steps)])
    assert cov.reader("nuts_lockstep_waste")(run) == pytest.approx(
        100 * (1 - 18 / 24))


def test_per_grad_readers():
    cov = spec.Cell("covtype_nuts4")
    fit = Fit({"sample": np.array([[1] * 10, [1] * 10, [1] * 10, [0] * 10])})
    ops = [("leapfrog_halfstep.1", i * MS, i * MS + 10) for i in range(10)]
    run = Run("covtype_nuts4", ops, [fit])
    assert run.window_grads == 30
    assert cov.reader("integrator_us_per_grad")(run) == pytest.approx(
        0.1 / 30)


def test_a_cut_trace_reports_no_per_gradient_or_idle_reading():
    cov = spec.Cell("covtype_nuts4")
    fit = Fit({"sample": np.ones((4, 10))})
    ops = [("leapfrog_halfstep.1", i * MS, i * MS + 10) for i in range(5)]
    run = Run("covtype_nuts4", ops, [fit], dropped_at=50 * MS)
    assert run.window_grads is None
    for name in ("integrator_us_per_grad", "device_idle_share"):
        assert cov.reader(name)(run) is None


def test_readers_find_nothing_and_say_so():
    cov = spec.Cell("covtype_nuts4")
    run = Run("covtype_nuts4", [], [Fit({"sample": np.ones((4, 2))})])
    for name in ("glm_kernel_roofline", "integrator_us_per_grad",
                 "device_idle_share"):
        assert cov.reader(name)(run) is None
