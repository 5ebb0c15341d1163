"""The trace reduction, on a trace recorded on a TPU v5e (a 2-layer
h2o-danube at batch 2: one prefill, the first-token program and three
decode steps, with the benchmark's spans) and on hand-made events."""
import math
import pathlib

import pytest

import run

HERE = pathlib.Path(__file__).resolve().parent
tr = run.load_module(HERE.parent / "trace.py", "bench_trace")


@pytest.fixture(scope="module")
def recorded():
    return tr.Trace.read(HERE / "data" / "probe_trace.json.gz")


def test_op_and_module_names():
    assert tr.op_base("%fusion.12 = f32[2] fusion(...)") == "fusion"
    assert tr.op_base("%flash_attention.6 = bf16[2] custom-call(...)") == \
        "flash_attention"
    assert tr.op_base("%copy-done.1 = f32[2] copy-done(...)") == "copy-done"
    assert tr.module_base("jit_serve_step(986401375)") == "jit_serve_step"


def test_recorded_kernels_and_programs(recorded):
    red = tr.reduce(recorded)
    # the flash kernel once per layer in prefill, the decode kernel once per
    # layer in each of three steps; read by hand from the trace
    flash_t, flash_n = red.kernel_s["flash_attention"]
    _, decode_n = red.kernel_s["decode_attention"]
    prefill_t, prefill_n = red.module_s["jit_prefill"]
    _, step_n = red.module_s["jit_serve_step"]
    assert (flash_n, decode_n, prefill_n, step_n) == (2, 6, 1, 3)
    assert near(flash_t, 2 * 151243e-9)
    assert near(prefill_t, 1513702e-9)
    assert red.collective_s is None and red.exposed_collective_s is None


def test_recorded_busy_within_window(recorded):
    red = tr.reduce(recorded)
    assert 0 < red.busy_s < red.window_s
    # the while loops span their bodies: busy is the union of the rest
    ops = [o for o in recorded.ops[0] if o[0] not in tr.CONTROL_FLOW]
    merged = tr.union((s, e) for _, s, e, _ in ops)
    assert near(red.busy_s, sum(e - s for s, e in merged) * 1e-9)
    total_prog = sum(t for t, _ in red.module_s.values())
    assert red.busy_s <= total_prog + 1e-9


def test_recorded_gaps_and_skew(recorded):
    # the device ran the first prefill 0.9 ms "before" the host dispatched
    # it: the two clocks differ, and the skew puts spans over the gaps
    assert tr.host_skew(recorded, 0) == pytest.approx(-905566.0)
    red = tr.reduce(recorded)
    names = {n for n, _ in red.idle_gaps}
    assert "bench.token_read" in names
    gap_sum = sum(s for _, s in red.idle_gaps)
    assert gap_sum <= red.window_s - red.busy_s + 1e-9


def near(a, b):
    return math.isclose(a, b, rel_tol=1e-6, abs_tol=1e-15)


def _ev(name, s, e, kernel=False):
    return (name, float(s), float(e), kernel)


def test_collectives_exposed_and_hidden():
    t = tr.Trace(
        ops={0: [_ev("fusion", 0, 10), _ev("all-reduce", 5, 15),
                 _ev("fusion", 20, 30)],
             1: [_ev("fusion", 0, 30), _ev("all-gather-start", 10, 20)]},
        modules={0: [("jit_step", 0, 30)], 1: [("jit_step", 0, 30)]},
        spans=[], dispatches=[])
    red = tr.reduce(t)
    # chip 0: collective 10 ns, 5 of them under compute; chip 1: 10, all
    # hidden.  Means over two chips.
    assert near(red.collective_s, 10e-9)
    assert near(red.exposed_collective_s, 2.5e-9)
    assert near(red.busy_s, (25 + 30) / 2 * 1e-9)
    assert near(red.window_s, 30e-9)


def test_top_ops_by_program():
    t = tr.Trace(
        ops={0: [_ev("fusion", 0, 4), _ev("fusion", 10, 12),
                 _ev("my_kernel", 4, 9, True), _ev("while", 0, 12)]},
        modules={0: [("jit_a", 0, 9), ("jit_b", 10, 12)]},
        spans=[("bench.x", 9, 10)], dispatches=[("a", 0), ("b", 10)])
    red = tr.reduce(t)
    assert red.top_ops[0] == ["jit_a/my_kernel", pytest.approx(5e-9)]
    assert ["jit_a/fusion", pytest.approx(4e-9)] in red.top_ops
    (name, (kt, kn)), = red.kernel_s.items()
    assert (name, kn) == ("my_kernel", 1) and near(kt, 5e-9)
    assert red.idle_gaps == [["bench.x", pytest.approx(1e-9)]]


def test_load_reads_host_spans_of_a_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.decode_step"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    t = tr.load(sorted(tmp_path.glob("**/*.xplane.pb"))[-1])
    assert [s[0] for s in t.spans] == ["bench.decode_step"]
    assert t.ops == {}          # no TPU plane on the CPU
    with pytest.raises(ValueError):
        tr.reduce(t)
