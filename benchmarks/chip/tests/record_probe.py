"""Record the scoped probe trace that `test_scopes.py` reads, on a TPU:

    python3 benchmarks/chip/tests/record_probe.py [out.json.gz]

A 2-layer h2o-danube at batch 2 (prompts of the `azure_conv_2023` length),
built as the serve kind builds it: one prefill, the first-token program and
three decode steps, under the benchmark's `bench.*` spans, traced by
`jax.profiler` and kept as `scopes.Scoped` (by default in
`data/probe_scoped_trace.json.gz`).
"""
import json
import pathlib
import sys
import tempfile

HERE = pathlib.Path(__file__).resolve().parent
CHIP = HERE.parent
sys.path[:0] = [str(CHIP), str(CHIP.parents[1] / "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

import run  # noqa: E402

SPAN = jax.profiler.TraceAnnotation


def main() -> int:
    run.describe_device(1)
    mods = run.modules()
    kind = run.load_module(CHIP / "kinds" / "serve_static.py", "kind")
    scopes = run.load_module(CHIP / "scopes.py", "bench_scopes")
    c = run.load_json(CHIP / "configs" / "h2o-danube-1.8b.json")
    c["num_hidden_layers"] = 2
    traffic = dict(run.load_json(CHIP / "traffic" / "azure_conv_2023.json"),
                   batch=2)
    srv = kind.Server(c, traffic, mods.model.make_weights(c, 1),
                      model=mods.model)
    srv.warm()
    prompts = jax.device_put(mods.model.prompts(
        1, 0, 2, traffic["prompt_len"], c["vocab_size"]))
    prompts.block_until_ready()
    out = tempfile.mkdtemp(prefix="probe-")
    jax.profiler.start_trace(out)
    with SPAN("bench.prefill"):
        logits, srv.caches = srv.prefill(srv.params, srv._reset(),
                                         {"tokens": prompts})
        tok = srv.first(logits)
    for _ in range(3):
        with SPAN("bench.decode_step"):
            tok, srv.caches = srv.step(srv.params, srv.caches, tok)
        with SPAN("bench.token_read"):
            np.asarray(tok)
    jax.profiler.stop_trace()
    xplane = sorted(pathlib.Path(out).glob("**/*.xplane.pb"))[-1]
    dest = (sys.argv[1] if len(sys.argv) > 1
            else HERE / "data" / "probe_scoped_trace.json.gz")
    scopes.load(xplane).save(dest)
    print(json.dumps({"xplane": str(xplane), "probe": str(dest)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
