"""The trace reduction on a synthetic trace with known answers."""
import pytest

from bench import trace as tm

MS = 1_000_000  # ns


def _trace(ops, host, dropped_at=None):
    return tm.Trace({"/device:TPU:0": ops}, host, "bench_fit", dropped_at)


HOST = [
    ("bench_fit", 0, 100 * MS, "python3"),
    ("warmup_chunk", 2 * MS, 60 * MS, "python3"),
    ("sample_chunk", 65 * MS, 98 * MS, "python3"),
    ("other thread", 0, 100 * MS, "main/1"),
]
OPS = [
    ("while.1", 10 * MS, 50 * MS),           # spans the two below
    ("glm_potential_grad.3", 12 * MS, 20 * MS),
    ("leapfrog_halfstep.2", 20 * MS, 21 * MS),
    ("glm_potential_grad.4", 72 * MS, 80 * MS),
    ("fusion.9", 79 * MS, 90 * MS),          # overlaps the one before
    ("fusion.9", 120 * MS, 130 * MS),        # outside the window
]


def test_op_name():
    text = "%jvp_glm_potential_grad_.14 = (f32[4,1,1]) custom-call(...)"
    assert tm.op_name(text) == "jvp_glm_potential_grad_.14"
    assert tm.op_name("fusion.3") == "fusion.3"


def test_merge():
    assert tm.merge([(5, 7), (1, 3), (2, 4), (7, 9)]) == [[1, 4], [5, 9]]


def test_busy_window_kernels():
    tr = _trace(OPS, HOST)
    assert tr.complete
    assert tr.window_s == pytest.approx(0.1)
    # [10, 50] and [72, 90] ms
    assert tr.busy_s == pytest.approx(0.058)
    assert tr.kernel("glm_potential_grad") == (pytest.approx(0.016), 2)
    assert tr.kernel("leapfrog_halfstep") == (pytest.approx(0.001), 1)
    assert tr.kernel("enum_contract") == (0.0, 0)


def test_top_ops_leave_out_control_flow():
    top = dict(_trace(OPS, HOST).top_ops())
    assert "while.1" not in top
    assert top["glm_potential_grad.3"] == pytest.approx(0.008)
    assert top["fusion.9"] == pytest.approx(0.011)   # the part in the window


def test_idle_gaps_by_host_span():
    gaps = dict(_trace(OPS, HOST).idle_gaps())
    # [0,10], [50,72] and [90,100] ms are idle; their middles fall in the
    # warmup chunk, between the chunks and in the sample chunk
    assert gaps == {"warmup_chunk": pytest.approx(0.010),
                    "(no host span)": pytest.approx(0.022),
                    "sample_chunk": pytest.approx(0.010)}


def test_dropped_buffers_end_the_window():
    tr = _trace(OPS, HOST, dropped_at=60 * MS)
    assert not tr.complete
    assert tr.span_s == pytest.approx(0.1)
    assert tr.window_s == pytest.approx(0.06)
    assert tr.busy_s == pytest.approx(0.040)
    assert tr.kernel("glm_potential_grad") == (pytest.approx(0.008), 1)


def test_no_device_ops():
    tr = tm.Trace({}, HOST, "bench_fit")
    assert tr.busy_s == 0.0
    assert tr.idle_gaps() == [["(no device ops)", pytest.approx(0.1)]]


def test_load_reads_a_recorded_trace(tmp_path):
    """A trace the profiler records on this machine's CPU: host spans are
    found and there is no TPU plane."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path),
                             profiler_options=tm.profile_options())
    with jax.profiler.TraceAnnotation("bench_fit"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = tm.load(tm.xplane_file(str(tmp_path)), "bench_fit")
    assert tr.window_s > 0 and tr.complete and tr.devices == {}
