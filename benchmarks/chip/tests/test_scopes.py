"""The scope and host-thread reduction (`scopes.py`) and the readers that
use it: on a trace recorded on a TPU v5e with the program's scopes (a
2-layer h2o-danube at batch 2: one prefill, the first-token program and
three decode steps, with the benchmark's spans; `record_probe.py`), on
hand-made events and on a CPU trace."""
import math
import pathlib
import types

import pytest

import run

CHIP = pathlib.Path(__file__).resolve().parents[1]

tr = run.load_module(CHIP / "trace.py", "bench_trace")
sc = run.load_module(CHIP / "scopes.py", "bench_scopes")


PROBE = CHIP / "tests" / "data" / "probe_scoped_trace.json.gz"


@pytest.fixture(scope="module")
def probe():
    return sc.Scoped.read(PROBE)


def reader(name):
    return run.load_module(CHIP / "metrics" / f"{name}.py",
                           "metric_" + name)


def near(a, b):
    return math.isclose(a, b, rel_tol=1e-6, abs_tol=1e-15)


@pytest.mark.parametrize("op_name, path", [
    ("jit(serve_step)/while/body/closed_call/attn/jit(decode_attention)/"
     "kv/jit(_pad)/pad", "attn/kv"),
    ("jit(serve_step)/while/body/closed_call/attn/jit(decode_attention)/"
     "kernel/decode_attention/pallas_call", "attn/kernel/decode_attention"),
    ("jit(prefill)/while/body/closed_call/attn/qkv/bsd,dhk->bshk/"
     "dot_general", "attn/qkv"),
    ("jit(serve_step)/embed/jit(_take)/gather", "embed"),
    ("jit(serve_step)/while/body/dynamic_slice", ""),
    ("attn/core/sub;attn/rope/broadcast_in_dim", "attn/core"),
    ("", ""),
])
def test_scope_path(op_name, path):
    assert sc.scope_path(op_name) == path


def _pb(*fields) -> bytes:
    """A protobuf message of (field number, int or bytes or str) pairs."""
    def varint(v):
        out = b""
        while True:
            out += bytes([v & 0x7F | (0x80 if v > 0x7F else 0)])
            v >>= 7
            if not v:
                return out
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += varint(num << 3) + varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += varint(num << 3 | 2) + varint(len(v)) + v
    return out


def test_tf_ops_reads_event_metadata_stats(tmp_path):
    # an XSpace with one device plane: `tf_op` as a string and as a
    # reference to a stat metadata's name; a host plane is passed over
    stat = (5, _pb((1, 5), (2, _pb((1, 5), (2, "tf_op")))))
    ref = (5, _pb((1, 9), (2, _pb((1, 9), (2, "jit(f)/mlp/dot:")))))
    kv = (4, _pb((1, 7), (2, _pb((1, 7), (2, "%pad.1 = ..."), (5, _pb(
        (1, 5), (5, "jit(f)/attn/kv/pad:")))))))
    mlp = (4, _pb((1, 8), (2, _pb((1, 8), (5, _pb((1, 5), (7, 9)))))))
    bare = (4, _pb((1, 6), (2, _pb((1, 6), (2, "%copy.2 = ...")))))
    line = (3, _pb((2, "XLA Ops"), *[(4, _pb((1, m), (2, 10), (3, 5)))
                                     for m in (7, 8, 6, 7)]))
    device = _pb((2, "/device:TPU:0"), stat, ref, kv, mlp, bare, line)
    host = _pb((2, "/host:CPU"), (3, _pb((2, "python3"))))
    (tmp_path / "t.xplane.pb").write_bytes(_pb((1, host), (1, device)))
    names = sc.tf_ops(tmp_path / "t.xplane.pb")
    assert names == {"/device:TPU:0": [
        "jit(f)/attn/kv/pad:", "jit(f)/mlp/dot:", "", "jit(f)/attn/kv/pad:"]}
    assert [sc.scope_path(n) for n in names["/device:TPU:0"]] == [
        "attn/kv", "mlp", "", "attn/kv"]


def _op(name, scope, s, e):
    return (name, scope, float(s), float(e))


def _scoped(ops, modules, spans=(), dispatches=(), host=(),
            python=("py#0", "py#1"), main="py#0"):
    base = tr.Trace(
        ops={c: [(n, s, e, False) for n, _, s, e in v]
             for c, v in ops.items()},
        modules=modules, spans=list(spans), dispatches=list(dispatches))
    return sc.Scoped(base, ops, sorted(host, key=lambda h: h[2]),
                     list(python), main)


def test_scope_seconds_by_program():
    s = _scoped(
        ops={0: [_op("fusion", "embed", 0, 2),
                 _op("copy", "attn/kv", 2, 5),
                 _op("while", "", 0, 9),
                 _op("decode_attention", "attn/kernel", 5, 6),
                 _op("dynamic-slice", "", 6, 9),
                 _op("fusion", "attn/kv", 12, 14)]},
        modules={0: [("jit_step", 0, 9), ("jit_prefill", 10, 14)]})
    red = sc.reduce(s)
    step = red.scope_s["jit_step"]
    # the while loop spans its body and is left out, as in trace.reduce
    assert set(step) == {"embed", "attn/kv", "attn/kernel", sc.UNSCOPED}
    assert near(step["attn/kv"], 3e-9) and near(step[sc.UNSCOPED], 3e-9)
    assert near(red.scope_s["jit_prefill"]["attn/kv"], 2e-9)
    assert red.top(2) == [["jit_step/(unscoped)", pytest.approx(3e-9)],
                          ["jit_step/attn/kv", pytest.approx(3e-9)]]
    # every op of the window falls in some scope or in (unscoped)
    total = sum(t for by in red.scope_s.values() for t in by.values())
    assert near(total, 11e-9)


def test_idle_gaps_name_what_each_thread_did():
    # device idle from 4 to 10 and 12 to 13; host and device clocks agree
    s = _scoped(
        ops={0: [_op("fusion", "mlp", 0, 4), _op("fusion", "mlp", 10, 12),
                 _op("fusion", "mlp", 13, 14)]},
        modules={0: [("jit_step", 0, 4), ("jit_step", 10, 12),
                     ("jit_step", 13, 14)]},
        spans=[("bench.decode_step", 3, 12)],
        dispatches=[("step", 0), ("step", 10), ("step", 13)],
        host=[("py#0", "bench.decode_step", 3, 12),
              ("py#0", "$array.py:1 __array__", 5, 9),
              ("py#0", "PjitFunction(step)", 10, 11),
              ("py#1", "bench.input_put", 7, 8),
              ("tpu#2", "TpuExecute", 2, 11),
              ("tpu#2", "Wait", 6.5, 7.5)])
    red = sc.reduce(s)
    base = tr.reduce(s.trace)
    # the gaps are trace.reduce's, in its order and with its names
    assert [[g[0], g[3]] for g in red.idle_gap_hosts] == base.idle_gaps
    (span, main, rt, t), (span2, main2, rt2, _) = red.idle_gap_hosts
    # the other Python thread's later span is not the runtime's
    assert (span, main, rt) == ("bench.decode_step",
                                "$array.py:1 __array__", "Wait")
    assert near(t, 6e-9)
    # nothing covers the middle of the second gap on any thread
    assert (span2, main2, rt2) == ("no bench span", "none", "none")


def test_host_of_a_gap_is_read_at_its_middle():
    s = _scoped(
        ops={0: [_op("fusion", "mlp", 0, 2), _op("fusion", "mlp", 8, 10)]},
        modules={0: [("jit_step", 0, 10)]},
        dispatches=[("step", 0)],
        host=[("py#0", "early", 2, 4), ("py#0", "middle", 4.5, 5.5),
              ("rt#1", "outer", 1, 9), ("rt#1", "inner", 3, 6)])
    (_, main, rt, t), = sc.reduce(s).idle_gap_hosts
    assert (main, rt) == ("middle", "inner") and near(t, 6e-9)


def test_window_is_trace_reduce_window():
    s = _scoped(
        ops={0: [_op("fusion", "mlp", 100, 200)]},
        modules={0: [("jit_step", 100, 200)]},
        spans=[("bench.prefill", 40, 90), ("bench.token_read", 210, 260)],
        dispatches=[("step", 60)])
    lo, hi, skew = sc.window(s.trace)
    assert (hi - lo) * 1e-9 == pytest.approx(tr.reduce(s.trace).window_s)
    assert skew == 40.0


def _reading(red, base):
    return types.SimpleNamespace(scopes=red, reduced=base)


def test_kv_readers_per_execution():
    s = _scoped(
        ops={0: [_op("pad", "attn/kv", 0, 2), _op("copy", "attn/kv", 2, 3),
                 _op("decode_attention", "attn/kernel", 3, 4),
                 _op("pad", "attn/kv", 10, 12), _op("fusion", "mlp", 12, 14),
                 _op("scatter", "attn/kv", 20, 25),
                 _op("flash_attention", "attn/kernel/flash_attention",
                     25, 30)]},
        modules={0: [("jit_serve_step", 0, 4), ("jit_serve_step", 10, 14),
                     ("jit_prefill", 20, 30)]})
    r = _reading(sc.reduce(s), tr.reduce(s.trace))
    assert reader("decode_kv_ms").read(r) == pytest.approx(2.5e-6)
    assert reader("prefill_kv_ms").read(r) == pytest.approx(5e-6)


def test_kv_readers_without_scopes():
    s = _scoped(ops={0: [_op("pad", "", 0, 2)]},
                modules={0: [("jit_serve_step", 0, 2)]})
    base = tr.reduce(s.trace)
    for name in ("decode_kv_ms", "prefill_kv_ms"):
        # the parent's program names no scope; a reading without the
        # scope reduction has nothing to read
        assert reader(name).read(_reading(sc.reduce(s), base)) is None
        assert reader(name).read(types.SimpleNamespace(reduced=base)) is None


def test_load_keeps_threads_and_scopes_of_a_cpu_trace(tmp_path):
    import threading

    import jax
    import jax.numpy as jnp
    import numpy as np

    @jax.jit
    def f(x):
        with jax.named_scope("mlp"):
            return jnp.tanh(x @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))

    def put():                  # as the prompt prefetcher puts prompts
        with jax.profiler.TraceAnnotation("bench.input_put"):
            jax.device_put(np.ones(4)).block_until_ready()
    t = threading.Thread(target=put)
    t.start()
    t.join()
    with jax.profiler.TraceAnnotation("bench.decode_step"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    s = sc.load(sorted(tmp_path.glob("**/*.xplane.pb"))[-1])
    threads = {h[1]: h[0] for h in s.host if h[1].startswith("bench.")}
    # the span of the prefetching thread lies on a thread of its own, and
    # the main thread is the one that dispatched the program
    assert threads["bench.decode_step"] == s.main
    assert threads["bench.input_put"] != s.main
    assert set(threads.values()) <= set(s.python)
    assert any(h[0] not in s.python for h in s.host)   # the runtime's
    assert [n for n, *_ in s.trace.spans] == ["bench.input_put",
                                              "bench.decode_step"]
    assert s.ops == {}          # no TPU plane on the CPU


def test_save_and_read(tmp_path):
    s = _scoped(ops={0: [_op("pad", "attn/kv", 0, 2)]},
                modules={0: [("jit_serve_step", 0, 2)]},
                spans=[("bench.decode_step", 0, 2)],
                dispatches=[("serve_step", 0)],
                host=[("py#0", "bench.decode_step", 0, 2)])
    s.save(tmp_path / "t.json.gz")
    back = sc.Scoped.read(tmp_path / "t.json.gz")
    assert back == s
    # trace.py reads the same file as it reads its own
    assert tr.Trace.read(tmp_path / "t.json.gz") == s.trace


def _op_seconds(s, program):
    """Device seconds of the program's ops on chip 0 (no control flow)."""
    runs = [(b, e) for n, b, e in s.trace.modules[0] if n == program]
    return sum((e - b) * 1e-9 for n, _, b, e in s.ops[0]
               if n not in tr.CONTROL_FLOW
               and any(rb <= b < re_ for rb, re_ in runs))


def test_probe_reads_as_the_old_probe_does(probe):
    # the fields trace.py reads, and what its readers find by name
    assert tr.Trace.read(PROBE) == probe.trace
    red = tr.reduce(probe.trace)
    assert {k: n for k, (_, n) in red.kernel_s.items()} == {
        "flash_attention": 2, "decode_attention": 6}
    assert {k: n for k, (_, n) in red.module_s.items()} == {
        "jit_prefill": 1, "jit__first_token": 1, "jit_serve_step": 3}


def test_probe_scopes(probe):
    red = sc.reduce(probe)
    base = tr.reduce(probe.trace)
    for prog in ("jit_prefill", "jit_serve_step"):
        by = red.scope_s[prog]
        # every op of the program is counted once, scoped or not
        assert near(sum(by.values()), _op_seconds(probe, prog))
        assert {"embed", "norm", "attn/qkv", "attn/rope", "attn/kv",
                "attn/out", "mlp", "unembed"} <= set(by)
    # a kernel's scope holds the kernel's custom call and nothing else
    for prog, kernel in (("jit_prefill", "flash_attention"),
                         ("jit_serve_step", "decode_attention")):
        assert near(red.scope_s[prog][f"attn/kernel/{kernel}"],
                    base.kernel_s[kernel][0])


def test_probe_gaps_name_the_main_thread(probe):
    red = sc.reduce(probe)
    base = tr.reduce(probe.trace)
    assert [[g[0], g[3]] for g in red.idle_gap_hosts] == base.idle_gaps
    # the three longest: the host reads each step's token while the chip
    # waits for the next step, inside jax.Array's value
    for span, main, _, _ in red.idle_gap_hosts[:3]:
        assert (span, main) == ("bench.token_read", "$array.py:631 _value")
    spans = {h[0] for h in probe.host if h[1].startswith("bench.")}
    assert spans == {probe.main} and probe.main in probe.python


def test_probe_kv_readers(probe):
    r = types.SimpleNamespace(scopes=sc.reduce(probe),
                              reduced=tr.reduce(probe.trace))
    for kv, whole in (("decode_kv_ms", "decode_step_ms"),
                      ("prefill_kv_ms", "prefill_ms")):
        v = reader(kv).read(r)
        assert 0 < v < reader(whole).read(r)
