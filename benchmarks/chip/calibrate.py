"""Readings that the correctness limits are set from, for one serve cell.

    python3 benchmarks/chip/calibrate.py --workload <cell> \
        --seeds 1 2 3 ... --control-seeds 1 2 3 [--out FILE]

In one process (the programs compile once): for each seed, new weights
and prompts, one batch through the timed path at the cell's own size, and
the compared number (`max_logit_gap`) over the same seeded sample of
requests that a run checks: the program's lower reading.  For each control
seed, the same prompts and served tokens through the reference in float8
(`reference.logits(..., quant="fp8")`): at each position the token the
control puts first, and how far its reference logit lies below the
reference's best: the control's reading.  A limit lies between the largest
program reading and the smallest control reading.

Each reading is one JSON line on standard output (and in `--out`).  The
benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import jax.numpy as jnp  # noqa: E402

import run  # noqa: E402


def control_gap(mods, c, params, rows, first):
    """Widest gap, in the reference, of the tokens the control puts first."""
    ref = mods.reference.logits(params, c, rows, first=first)
    ctl = mods.reference.logits(params, c, rows, first=first, quant="fp8")
    return mods.reference.widest_gap(ref, jnp.argmax(ctl, -1))


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
    traffic = run.load_json(HERE / "traffic" / f"{w['traffic']}.json")
    dev = run.describe_device(w["chips"])
    run.enable_cache()
    mods = run.modules()
    kind = run.load_module(HERE / "kinds" / f"{traffic['kind']}.py",
                           "kind_" + traffic["kind"])
    out = open(args.out, "a") if args.out else None
    B, P = traffic["batch"], traffic["prompt_len"]
    server = params = None
    for seed in sorted(set(args.seeds) | set(args.control_seeds)):
        t0 = time.perf_counter()
        if server is not None:
            server.params = params = None      # one set of weights at a time
        params = mods.model.make_weights(c, seed)
        if server is None:
            server = kind.Server(c, traffic, params, model=mods.model)
            server.warm()
        server.params = params
        src = kind.prompt_source(seed, B, P, c["vocab_size"], mods.model)
        batch = server.serve_batch(0, next(src),
                                   kind.batch_lengths(traffic, seed, 0))
        rec = {"workload": w["name"], "seed": seed, "device": dev["kind"]}
        if seed in args.seeds:
            chk = kind.check([batch], seed, traffic, c, params, mods.model,
                             mods.reference)
            rec["program_max_logit_gap"] = chk["max_logit_gap"]
        if seed in args.control_seeds:
            rows, _ = kind.sample_rows([batch], seed, traffic, c,
                                       mods.model)
            rec["control_max_logit_gap"] = control_gap(mods, c, params,
                                                       rows, P - 1)
        rec["seconds"] = time.perf_counter() - t0
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
