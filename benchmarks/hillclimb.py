"""§Perf hillclimb driver: three cells, hypothesis -> change -> measure.

Runs each optimization variant through the dry-run (512 host devices), so
it MUST be executed as its own process:

    PYTHONPATH=src python -m benchmarks.hillclimb [--cell A|B|C]

Cells (chosen per the assignment rubric from the baseline table):
  A: llama3-405b x train_4k  (worst roofline fraction among the big dense
     cells; most representative of the paper's LLM-training workload §5.3)
  B: kimi-k2-1t-a32b x train_4k  (compute-term dominated by MoE dispatch)
  C: h2o-danube-1.8b x decode_32k multi  (most collective-bound cell)

Each variant writes a tagged artifact next to the baselines; the log of
hypothesis/result pairs is artifacts/hillclimb.jsonl, rendered into
EXPERIMENTS.md §Perf.
"""
import argparse
import dataclasses
import json
import pathlib
import sys
import traceback

ART = pathlib.Path(__file__).resolve().parents[1] / "artifacts"


def _run(cell_name, step_label, hypothesis, **kw):
    from repro.launch.dryrun import run_cell
    rec = run_cell(**kw)
    out = {"cell": cell_name, "variant": kw.get("tag", "baseline"),
           "step": step_label, "hypothesis": hypothesis,
           "status": rec.get("status"),
           "roofline": rec.get("roofline"),
           "collectives": rec.get("collectives"),
           "bytes_per_device": rec.get("bytes_per_device")}
    with open(ART / "hillclimb.jsonl", "a") as f:
        f.write(json.dumps(out) + "\n")
    r = rec.get("roofline", {})
    print(f"[{cell_name}/{kw.get('tag','baseline')}] "
          f"t_c={r.get('t_compute', 0):.3e} t_m={r.get('t_memory', 0):.3e} "
          f"t_x={r.get('t_collective', 0):.3e} "
          f"bneck={r.get('bottleneck')} roof={r.get('roofline_fraction', 0):.4f}",
          flush=True)
    return out


def cell_a():
    """llama3-405b train_4k: memory-term (naive-attention bytes) hillclimb."""
    base = dict(arch="llama3-405b", shape_name="train_4k", mesh_kind="single")
    _run("A", 1, "baseline: naive attention materializes O(S^2) fp32 "
         "scores -> memory term dominated by ~B*H*S^2*4 bytes/layer", **base)
    _run("A", 2, "chunked online-softmax attention (attn_block=1024) "
         "removes S^2 score traffic; predict t_memory drops ~5-10x and "
         "bottleneck stays memory (params+activations remain)",
         tag="attn_chunked", attn_block=1024, **base)
    _run("A", 3, "remat off on top of chunked attention: recompute flops "
         "fall (t_compute down ~25%), activation bytes rise; predict "
         "worse t_memory — checking the trade",
         tag="attn_chunked_noremat", attn_block=1024, remat=False, **base)


def cell_b():
    """kimi-k2 train_4k: compute term (MoE einsum dispatch) hillclimb."""
    from repro.configs import get_config
    moe = get_config("kimi-k2-1t-a32b").moe
    scatter = {"moe": dataclasses.replace(moe, dispatch="scatter")}
    base = dict(arch="kimi-k2-1t-a32b", shape_name="train_4k",
                mesh_kind="single")
    _run("B", 1, "baseline: GShard one-hot dispatch costs 2*N*E*C*D flops "
         "per MoE layer (E=384) — predicted to dwarf the 2*N*D_active "
         "useful matmuls", **base)
    _run("B", 2, "scatter/gather dispatch: replace dispatch einsums with "
         "O(N*K*D) scatter-add + gather; predict t_compute drops ~5-8x "
         "(expert FFN matmuls become dominant)",
         tag="moe_scatter", cfg_overrides=scatter, **base)
    _run("B", 3, "scatter dispatch + chunked attention: also remove the "
         "S^2 attention bytes; predict memory term drops too",
         tag="moe_scatter_attn", attn_block=1024,
         cfg_overrides=scatter, **base)


def cell_c():
    """h2o-danube decode_32k multi: collective term hillclimb."""
    base = dict(arch="h2o-danube-1.8b", shape_name="decode_32k",
                mesh_kind="multi")
    _run("C", 1, "baseline: FSDP param sharding forces per-step all-gather "
         "of every layer's weights to decode ONE token -> collective-bound",
         **base)
    _run("C", 2, "TP-only params (fsdp=False): 1.8B bf16 params fit "
         "replicated over batch axes (225MB/chip at TP=16); predict the "
         "all-gather term collapses to ~0 and bottleneck flips to memory",
         tag="no_fsdp", fsdp=False, **base)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", default="all", choices=["A", "B", "C", "all"])
    args = ap.parse_args()
    fns = {"A": [cell_a], "B": [cell_b], "C": [cell_c],
           "all": [cell_a, cell_b, cell_c]}[args.cell]
    for fn in fns:
        try:
            fn()
        except Exception:   # noqa: BLE001
            traceback.print_exc()
            sys.exit(1)


if __name__ == "__main__":
    main()
