"""Serve traffic on a hybrid (Mamba, attention, MoE) configuration: the
closed loop of static batches of `serve_static.py`, over the modules of
`hybrid/` (the configuration's program config, weights and prompts, its
float32 reference, its FLOP and byte counts) in place of the dense
decoder's.

The window and the end-to-end metrics are `serve_static`'s; the check
compares the same sample of requests with the float32 reference and is
held to the mean of the gaps (`check`).  The traced batch's work: one flash
attention call in prefill and one decode-attention call a step per
attention layer, and decode bytes from the program's MoE routing counter
(kept in each MoE layer's cache: tokens routed to each held expert in
prefill and in decode, and the decode steps in which it got any), so that
a step is charged the held experts it read.  The traced run also reduces
the trace by the program's scopes (`scopes.py`) and hands that reduction
to the per-layer readers as `work["scopes"]`.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import pathlib
import sys
import time
import types

import jax
import numpy as np

CHIP = pathlib.Path(__file__).resolve().parents[1]


def _load(path: pathlib.Path, name: str):
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


static = _load(CHIP / "kinds" / "serve_static.py", "kind_serve_static")
hybrid = types.SimpleNamespace(
    model=_load(CHIP / "hybrid" / "model.py", "hybrid_model"),
    reference=_load(CHIP / "hybrid" / "reference.py", "hybrid_reference"),
    flops=_load(CHIP / "hybrid" / "flops.py", "hybrid_flops"))
# serve_static's loop, as `hybrid/calibrate.py` and the tests reach it
Server, serve_window, e2e_metrics, prompt_source, batch_lengths, \
    sample_rows = (static.Server, static.serve_window, static.e2e_metrics,
                   static.prompt_source, static.batch_lengths,
                   static.sample_rows)


def check(batches, seed, traffic, c, params, model, reference) -> dict:
    """`serve_static.check`'s comparison with the float32 reference, over
    the same seeded sample of answered requests, giving the mean of the
    gaps (`reference.gaps`) beside the widest.

    The limit is on the mean: a bf16 program departs from the float32
    reference wherever its MoE routes a token to another expert than the
    reference does (the top 2 of 16 on a near tie; 0.3 % of the tokens of
    a layer at the cell's widths, whose output then differs by half its
    size), and the Mamba mixers carry such a difference on into later
    tokens, so a few served tokens lie far below the reference's best in
    some seeds: the widest gap of a sound program reaches that of a
    program computing in float8, while the mean stays several times
    apart."""
    bad = int(sum(((b.tokens < 0) | (b.tokens >= c["vocab_size"])).any(1)
                  .sum() for b in batches))
    rows, served = sample_rows(batches, seed, traffic, c, model)
    ref = reference.logits(params, c, rows, first=traffic["prompt_len"] - 1)
    g = reference.gaps(ref, served)
    return {"checked_tokens": served.size, "bad_requests": bad,
            "max_logit_gap": float(g.max()),
            "mean_logit_gap": float(g.mean())}


def moe_counts(caches) -> np.ndarray:
    """The routing counters of the MoE layers, (layers, held experts, 3):
    tokens routed in prefill, tokens routed in decode, decode steps hit."""
    return np.concatenate([np.asarray(c["moe"]) for c in caches["layers"]
                           if "moe" in c])


def traced_work(c: dict, traffic: dict, flops, counts: np.ndarray) -> dict:
    """What one batch asks of the device.  Model FLOPs count the prompts
    and each request's own answer tokens, the held experts at the share of
    assignments the counter saw go to them; the steps' bytes and the
    kernels' work count every row, as the step runs them, and a step's
    experts are the held ones that got a token (the counter's steps hit,
    summed over experts and layers, spread evenly over the steps)."""
    B, P = traffic["batch"], traffic["prompt_len"]
    lengths = static.answer_lengths(traffic)
    G = int(lengths.max())
    H, K, d = flops.head(c)
    n_attn = flops.layer_counts(c)["attn"]
    n_moe = counts.shape[0]
    per_prefill = counts[..., 0].sum() / n_moe / (B * P)
    per_decode = counts[..., 1].sum() / n_moe / (B * (G - 1))
    hit = counts[..., 2].sum() / (G - 1)
    work = {"model_flops": flops.forward_flops(c, B, P, per_prefill),
            "decode_bytes": 0,
            "kernels": {"flash_attention": [
                (*flops.flash_fwd(B, P, H, K, d), n_attn)],
                "decode_attention": []},
            "moe_counts": counts, "experts_hit_per_step": hit,
            "moe_decode_bytes": (counts[..., 2].sum() * flops.expert_bytes(c)
                                 + (G - 1) * n_moe * flops.F32
                                 * c["hidden_size"]
                                 * c["num_experts_published"])}
    for k in range(1, G):                  # step k writes position P+k-1
        asked = int((lengths > k).sum())
        work["model_flops"] += flops.forward_flops(c, asked, 1, per_decode,
                                                   past=P + k - 1)
        work["decode_bytes"] += flops.decode_step_bytes(c, B, P + k, hit)
        work["kernels"]["decode_attention"].append(
            (*flops.decode_attn(B, P + k, H, K, d), n_attn))
    return work


# XLA's ragged-dot kernels (and their metadata ops) carry no scope in their
# `op_name`; the program's only ragged dots are the MoE's grouped matmuls
GROUPED = ("ragged-dot", "mlp/moe/experts")


def scoped(trace_dir):
    """`scopes.py`'s reduction of the traced batch, the ragged-dot kernels
    put under the scope of the grouped matmuls that they are."""
    scopes = _load(CHIP / "scopes.py", "bench_scopes")
    xplanes = sorted(pathlib.Path(trace_dir).glob("**/*.xplane.pb"))
    sc = scopes.load(xplanes[-1])
    prefix, scope = GROUPED
    sc.ops = {chip: [(b, s or (scope if b.startswith(prefix) else s), t0, t1)
                     for b, s, t0, t1 in ops] for chip, ops in sc.ops.items()}
    return scopes.reduce(sc)


def run(cell, mods) -> dict:
    """One run of a hybrid serve cell; see `run.py` for what it returns."""
    model, reference, flops = hybrid.model, hybrid.reference, hybrid.flops
    c, traffic = cell.config, cell.traffic
    t = [time.perf_counter()]
    params = model.make_weights(c, cell.seed)
    jax.block_until_ready(params)
    t.append(time.perf_counter())
    server = Server(c, traffic, params, model=model)
    t.append(time.perf_counter())
    server.warm()
    gc.collect()            # as serve_static: no full passes in the window
    gc.freeze()
    t.append(time.perf_counter())
    out = {"setup_end": t[-1]}
    u0 = static.usage()
    with mods.count_compiles() as compiles:
        batches, t_open, t_close = serve_window(
            server, cell.seed, cell.seconds, traffic, cell.trace_dir)
    u1 = static.usage()
    out["compiles_in_window"] = compiles.n
    out["memory_peak_bytes"] = mods.memory_peak()
    counts = moe_counts(server.caches)
    server.release()
    G = server.G
    print("moe_counter " + json.dumps({     # the last batch's routing
        "held_share_prefill": float(counts[..., 0].sum() / counts.shape[0]
                                    / (traffic["batch"] * traffic[
                                        "prompt_len"] * c[
                                        "num_experts_per_tok"])),
        "experts_hit_per_step": float(counts[..., 2].sum() / (G - 1)),
        "hit_steps_by_layer": counts[..., 2].sum(1).tolist()}),
        file=sys.stderr)
    if cell.trace_dir is None:
        out["e2e"] = e2e_metrics(batches, t_open, t_close)
    else:
        out["work"] = traced_work(c, traffic, flops, counts)
        out["work"]["scopes"] = red = scoped(cell.trace_dir)
        print("device_scopes " + json.dumps(red.top(40)), file=sys.stderr)
    out["host"] = dict(static.host_gaps(batches), **dict(zip(
        ("wall_s", "cpu_s", "involuntary_switches", "major_faults"),
        (b - a for a, b in zip(u0, u1)))))
    out["attempted"] = traffic["batch"] * len(batches)
    t.append(time.perf_counter())
    out["checks"] = check(batches, cell.seed, traffic, c, params, model,
                          reference)
    out["failed"] = out["checks"]["bad_requests"]
    print("gaps " + json.dumps({k: out["checks"][k] for k in (
        "max_logit_gap", "mean_logit_gap")}), file=sys.stderr)
    out["seconds"] = {"weights": t[1] - t[0], "compile": t[2] - t[1],
                      "warm": t[3] - t[2], "window_and_tail": t[4] - t[3],
                      "check": time.perf_counter() - t[4]}
    return out
