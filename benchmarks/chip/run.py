"""Chip benchmark: run one cell of `BENCHMARK.json` once.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Everything is found by name: the cell in `BENCHMARK.json`, its
configuration in `configs/<config>.json`, its traffic in
`traffic/<traffic>.json`, the loop for that traffic's `kind` in
`kinds/<kind>.py`, each per-layer metric's reader in `metrics/<name>.py`,
the cell's correctness limits in `limits/<cell>.json`, and the device's
peaks in `peaks.json` by `device_kind`.  A new cell, configuration, traffic
mix or metric is new files and entries.

The run fails (non-zero exit, no result) when JAX finds no TPU or fewer
chips than the cell asks for.  `setup_s` runs from the start of this
process to the opening of the window: weights made on the device from the
seed, the programs compiled (from the persistent cache after a cell's
first run) and warmed at the cell's shapes.  With `--trace 0` the result
holds the cell's end-to-end metrics; with `--trace 1` its per-layer
metrics, read from a profiler trace of one batch.  Either way the run then
checks what the timed path produced against the float32 reference.

The last line of standard output is one JSON object; the numbers compared
for `correct` follow, each beside its limit, as the last lines of standard
error and as the result's last key.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                                # noqa: E402
import contextlib                                              # noqa: E402
import importlib.util                                          # noqa: E402
import json                                                    # noqa: E402
import pathlib                                                 # noqa: E402
import shutil                                                  # noqa: E402
import sys                                                     # noqa: E402
import tempfile                                                # noqa: E402
import types                                                   # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def load_module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_json(path: pathlib.Path) -> dict:
    return json.loads(path.read_text())


class Failure(RuntimeError):
    """The run cannot produce a result."""


def describe_device(chips: int) -> dict:
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != "tpu":
        raise Failure(f"no TPU: JAX reports {dev['count']} "
                      f"{dev['platform']} device(s)")
    if dev["count"] < chips:
        raise Failure(f"the cell needs {chips} chips; JAX reports "
                      f"{dev['count']}")
    return dev


def peaks_of(kind: str) -> dict:
    table = load_json(HERE / "peaks.json")
    if kind not in table:
        raise Failure(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


class _Compiles:
    n = 0


@contextlib.contextmanager
def count_compiles():
    """Count programs traced, compiled or fetched from the compile cache
    while the block runs."""
    import jax
    box = _Compiles()

    def listen(event, *_, **__):
        if event.startswith(("/jax/core/compile",
                             "/jax/compilation_cache/cache_retrieval")):
            box.n += 1
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield box
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


def memory_peak() -> int:
    import jax
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.local_devices())


def enable_cache() -> str:
    import jax
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    # every program, however quick to compile, so that set-up is the same
    # from a cell's second run on
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def modules() -> types.SimpleNamespace:
    return types.SimpleNamespace(
        model=load_module(HERE / "model.py", "bench_model"),
        reference=load_module(HERE / "reference.py", "bench_reference"),
        flops=load_module(HERE / "flops.py", "bench_flops"),
        trace=load_module(HERE / "trace.py", "bench_trace"),
        count_compiles=count_compiles, memory_peak=memory_peak)


def cell_of(bench: dict, workload: str) -> dict:
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise Failure(f"no workload {workload!r} in BENCHMARK.json")
    return cells[workload]


def applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def judge(checks: dict, limits: dict) -> tuple:
    """(correct, {name: {value, limit}}) — every limit is an upper one."""
    out = {k: {"value": checks[k], "limit": lim} for k, lim in limits.items()}
    ok = all(v["value"] <= v["limit"] for v in out.values())
    return ok, out


def per_layer(bench, workload, reading) -> dict:
    out = {}
    for m in bench["per_layer"]:
        if not applies(m, workload):
            continue
        reader = load_module(HERE / "metrics" / f"{m['name']}.py",
                             "metric_" + m["name"].replace(".", "_"))
        v = reader.read(reading)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = load_json(ROOT / "BENCHMARK.json")
    w = cell_of(bench, args.workload)
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = load_json(ROOT / conf["file"])
    traffic = load_json(HERE / "traffic" / f"{w['traffic']}.json")
    limits = load_json(HERE / "limits" / f"{w['name']}.json")
    t = [time.perf_counter()]
    dev = describe_device(w["chips"])
    t.append(time.perf_counter())
    peaks = peaks_of(dev["kind"])
    enable_cache()
    mods = modules()
    kind = load_module(HERE / "kinds" / f"{traffic['kind']}.py",
                       "kind_" + traffic["kind"])
    t.append(time.perf_counter())
    trace_dir = (pathlib.Path(tempfile.mkdtemp(prefix="bench-trace-"))
                 if args.trace else None)
    try:
        cell = types.SimpleNamespace(
            name=w["name"], config=config, traffic=traffic, seed=args.seed,
            seconds=args.seconds, chips=w["chips"], trace_dir=trace_dir)
        res = kind.run(cell, mods)
        print(f"compiles_in_window {res['compiles_in_window']}",
              file=sys.stderr)
        seconds = dict(zip(("imports", "devices", "cache_and_modules"),
                           (t[0] - T_START, t[1] - t[0], t[2] - t[1])),
                       **res["seconds"])
        print("seconds " + json.dumps(seconds), file=sys.stderr)
        print("host " + json.dumps(res["host"]), file=sys.stderr)
        device = dict(dev, memory_peak_bytes=res["memory_peak_bytes"])
        result = {"correct": None, "attempted": res["attempted"],
                  "failed": res["failed"]}
        if trace_dir is None:
            e2e = dict(res["e2e"], setup_s=res["setup_end"] - T_START)
            metrics = {}
            for m in bench["end_to_end"]:
                if applies(m, w["name"]):
                    if e2e.get(m["name"]) is None:
                        raise Failure(f"{m['name']} was not measured")
                    metrics[m["name"]] = {"value": e2e[m["name"]],
                                          "unit": m["unit"]}
            print("window " + json.dumps(
                {k: v for k, v in e2e.items() if k not in metrics}),
                file=sys.stderr)
        else:
            xplanes = sorted(trace_dir.glob("**/*.xplane.pb"))
            tr = mods.trace.load(xplanes[-1])
            red = mods.trace.reduce(tr)
            reading = types.SimpleNamespace(
                reduced=red, work=res["work"], peaks=peaks,
                chips=w["chips"], config=config, traffic=traffic)
            metrics = per_layer(bench, w["name"], reading)
            device.update(busy_s=red.busy_s, window_s=red.window_s)
            result["breakdown"] = {"device_ops": red.top_ops,
                                   "idle_gaps": red.idle_gaps}
        ok, checks = judge(res["checks"], limits)
        result.update(correct=ok, metrics=metrics, device=device,
                      checks=checks)
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    for k, v in checks.items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Failure as e:
        print(f"benchmark: {e}", file=sys.stderr)
        sys.exit(1)
