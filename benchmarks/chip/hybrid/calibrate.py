"""Readings that a hybrid serve cell's correctness limits are set from.

    python3 benchmarks/chip/hybrid/calibrate.py --workload jamba-chat \
        --seeds 1 2 3 ... --control-seeds 1 2 3 [--out FILE]

As `calibrate.py`, with this directory's model and reference and the
hybrid kind's check, in one process (the programs compile once): for each
seed, new weights and prompts, one batch through the timed path at the
cell's own size, and the compared numbers (`mean_logit_gap`, and
`max_logit_gap` beside it) over the seeded sample of requests that a run
checks: the program's lower readings.  For each control seed, the same
rows through the reference in float8 (`reference.logits(...,
quant="fp8")`): the gaps, in the float32 reference, of the tokens the
control puts first: the control's readings.  A limit lies between the
largest program reading and the smallest control reading.

Each reading is one JSON line on standard output (and in `--out`).
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

CHIP = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))

import jax.numpy as jnp  # noqa: E402

import run  # noqa: E402


def control_gaps(reference, c, params, rows, first):
    """The gaps, in the reference, of the tokens the control puts first."""
    ref = reference.logits(params, c, rows, first=first)
    ctl = reference.logits(params, c, rows, first=first, quant="fp8")
    return reference.gaps(ref, jnp.argmax(ctl, -1))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    bench = run.load_json(run.ROOT / "BENCHMARK.json")
    w = run.cell_of(bench, args.workload)
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    c = run.load_json(run.ROOT / conf["file"])
    traffic = run.load_json(CHIP / "traffic" / f"{w['traffic']}.json")
    dev = run.describe_device(w["chips"])
    run.enable_cache()
    kind = run.load_module(CHIP / "kinds" / f"{traffic['kind']}.py",
                           "kind_" + traffic["kind"])
    model, reference = kind.hybrid.model, kind.hybrid.reference
    out = open(args.out, "a") if args.out else None
    B, P = traffic["batch"], traffic["prompt_len"]
    server = params = None
    for seed in sorted(set(args.seeds) | set(args.control_seeds)):
        t0 = time.perf_counter()
        if server is not None:
            server.params = params = None      # one set of weights at a time
        params = model.make_weights(c, seed)
        if server is None:
            server = kind.Server(c, traffic, params, model=model)
            server.warm()
        server.params = params
        src = kind.prompt_source(seed, B, P, c["vocab_size"], model)
        batch = server.serve_batch(0, next(src),
                                   kind.batch_lengths(traffic, seed, 0))
        rec = {"workload": w["name"], "seed": seed, "device": dev["kind"]}
        if seed in args.seeds:
            chk = kind.check([batch], seed, traffic, c, params, model,
                             reference)
            rec.update(program_max_logit_gap=chk["max_logit_gap"],
                       program_mean_logit_gap=chk["mean_logit_gap"])
        if seed in args.control_seeds:
            rows, _ = kind.sample_rows([batch], seed, traffic, c, model)
            g = control_gaps(reference, c, params, rows, P - 1)
            rec.update(control_max_logit_gap=float(g.max()),
                       control_mean_logit_gap=float(g.mean()))
        rec["seconds"] = time.perf_counter() - t0
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
