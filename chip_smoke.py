"""Smoke run of the model stack on a TPU, through the launchers' entry points.

    python chip_smoke.py [--seed N]              # one chip
    python chip_smoke.py --four-chips [--seed N] # four chips, sharded train only

One chip runs h2o-danube-1.8b at its published widths with the Pallas
kernels compiled, in one process and in this order:

  device  platform, device kind and count; anything but a TPU exits 1.
  serve   `repro.launch.serve.serve` on all 24 layers: batch 8, prompt 2048,
          64 generated tokens.  Both compiled programs must hold the kernels
          (`tpu_custom_call`); compilation is kept out of the tokens/s.
  logits  the kernel path against `use_pallas=False` on the same weights,
          prompts and caches: prefill's last position and one decode step.
  train   `repro.launch.train.train_loop` at full width cut to 8 layers,
          batch 1 x 4096, 4 steps; every loss finite.

`--four-chips` runs only `train_loop` at full width cut to 4 layers, global
batch 2 x 4096, on a data=2 x model=2 mesh, against the same steps on one
chip of the same process.

Weights and data are made from `--seed`.  Every phase prints one JSON line;
any failed check raises, so the script exits non-zero and prints no result.
The last line of a passing run is
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}`.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

ARCH = "h2o-danube-1.8b"

# Kernel-vs-XLA logits limit: max|kernel - xla| / max|xla| over the logits
# compared.  Both paths hold activations in bf16 (8-bit mantissa, 2^-8 ~
# 0.4% per rounding).  The XLA path rounds attention scores and
# probabilities to bf16 where the kernels keep them in f32, so each layer's
# attention output differs by about one bf16 rounding, and the residual
# stream carries that through every layer as a random walk.  At these
# widths on the CPU (kernels interpreted) the error is 1.0% at 1 layer,
# 1.4% at 4 and 1.9% at 12, so ~2.5% at 24; 5% leaves room for the TPU's
# own matmul rounding.  A wrong mask, block or head mapping moves logits
# by the order of their own scale (>= 30%).
LOGITS_REL_TOL = 0.05

# Four-chip vs one-chip training loss limit, absolute, per step: the same
# bf16 program partitioned over 2 x 2 chips sums its matmuls in another
# order (partial sums across the model axis, gradients across the data
# axis), which moves a ~10.4 cross-entropy by ~1e-3; after an AdamW update
# such differences stay of that order.  A sharding fault (a dropped or
# doubled shard) moves it by far more than 0.02.
FOUR_CHIP_LOSS_ATOL = 0.02


class SmokeFailure(RuntimeError):
    pass


def require(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def device_phase(count: int) -> dict:
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    require(dev["platform"] == "tpu",
            f"no TPU found: JAX reports {dev['count']} {dev['platform']} "
            "device(s)")
    require(dev["count"] >= count,
            f"{count} chips needed, JAX reports {dev['count']}")
    emit("device", **dev)
    return dev


def serve_phase(cfg, *, batch, prompt_len, gen, seed) -> dict:
    from repro.launch.serve import serve
    toks, stats = serve(cfg, batch=batch, prompt_len=prompt_len, gen=gen,
                        seed=seed, use_pallas=True)
    toks = toks.tolist()
    require(len(toks) == batch and all(len(t) == gen for t in toks),
            f"generated tokens have the wrong shape: {len(toks)} rows")
    require(all(0 <= t < cfg.vocab_size for row in toks for t in row),
            "generated a token outside the vocabulary")
    emit("serve", batch=batch, prompt_len=prompt_len, gen=gen, **stats)
    return stats


def logits_phase(cfg, *, batch, prompt_len, gen, seed) -> dict:
    """Kernel path against the XLA path on the same weights and caches.

    `batch` may be below the serve phase's: the XLA path materialises the
    (S x S) attention scores, and the first `batch` prompts are the serve
    phase's first prompts."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.launch.serve import kernel_calls
    from repro.models import model as M
    from repro.train.steps import make_prefill

    params = M.init_params(jax.random.PRNGKey(seed), cfg, tp=1)
    caches = M.init_caches(cfg, batch, prompt_len + gen, tp=1)
    prompts = jax.random.randint(jax.random.PRNGKey(seed + 1),
                                 (batch, prompt_len), 0, cfg.vocab_size)

    def decode_logits(use_pallas):
        def f(params, caches, token):
            return M.decode_step(params, cfg, token, caches,
                                 use_pallas=use_pallas)[0]
        return f

    t0 = time.perf_counter()
    prefill = {p: jax.jit(make_prefill(cfg, use_pallas=p)).lower(
        params, caches, {"tokens": prompts}).compile() for p in (True, False)}
    decode = {p: jax.jit(decode_logits(p)).lower(
        params, caches, jax.ShapeDtypeStruct((batch, 1), jnp.int32)).compile()
        for p in (True, False)}
    compile_s = time.perf_counter() - t0

    pre = {p: prefill[p](params, caches, {"tokens": prompts})
           for p in (True, False)}
    # one decode step for both paths from the XLA path's caches and token
    xla_caches = pre[False][1]
    token = jnp.argmax(pre[False][0][:, -1], -1).astype(jnp.int32)[:, None]
    dec = {p: decode[p](params, xla_caches, token) for p in (True, False)}

    def rel_err(a, b):
        a = np.asarray(a, np.float32)[..., :cfg.vocab_size]
        b = np.asarray(b, np.float32)[..., :cfg.vocab_size]
        require(np.isfinite(a).all() and np.isfinite(b).all(),
                "non-finite logits")
        return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))

    out = {"batch": batch, "compile_s": compile_s,
           "prefill_rel_err": rel_err(pre[True][0], pre[False][0]),
           "decode_rel_err": rel_err(dec[True], dec[False]),
           "tol": LOGITS_REL_TOL,
           "prefill_kernel_calls": kernel_calls(prefill[True]),
           "decode_kernel_calls": kernel_calls(decode[True])}
    emit("logits", **out)
    require(out["prefill_rel_err"] <= LOGITS_REL_TOL,
            f"prefill logits differ: {out['prefill_rel_err']}")
    require(out["decode_rel_err"] <= LOGITS_REL_TOL,
            f"decode logits differ: {out['decode_rel_err']}")
    return out


def train_phase(cfg, *, steps, batch, seq, seed, use_pallas=True,
                data_mesh=1, model_mesh=1, phase="train"):
    from repro.launch.train import train_loop
    state, info = train_loop(cfg, steps=steps, batch=batch, seq=seq,
                             seed=seed, use_pallas=use_pallas,
                             data_mesh=data_mesh, model_mesh=model_mesh,
                             log_every=steps)
    losses = info["losses"]
    emit(phase, layers=cfg.num_layers, batch=batch, seq=seq,
         mesh=[data_mesh, model_mesh], use_pallas=use_pallas, losses=losses,
         first_step_s=info["first_step_s"],
         step_times_s=info["step_times_s"])
    require(len(losses) == steps, f"{len(losses)} of {steps} steps ran")
    require(all(math.isfinite(l) for l in losses),
            f"non-finite training loss: {losses}")
    return state, info


def four_chip_phase(cfg, *, steps, batch, seq, seed) -> None:
    """Sharded training on a data=2 x model=2 mesh against one chip.

    Both runs take the XLA attention path: a Pallas kernel inside a program
    that GSPMD partitions needs a shard_map around it, which the model does
    not have yet."""
    state, info = train_phase(cfg, steps=steps, batch=batch, seq=seq,
                              seed=seed, use_pallas=False, data_mesh=2,
                              model_mesh=2, phase="train_2x2")
    embed = state.params["embed"]
    param_devs = sorted(d.id for d in embed.sharding.device_set)
    batch_devs = sorted(d.id for d in info["batch_sharding"].device_set)
    sharded = info["losses"]
    del state, embed
    state, info = train_phase(cfg, steps=steps, batch=batch, seq=seq,
                              seed=seed, use_pallas=False, phase="train_1x1")
    single = info["losses"]
    one_devs = sorted(d.id for d in state.params["embed"].sharding.device_set)
    del state
    diffs = [abs(a - b) for a, b in zip(sharded, single)]
    emit("four_chips", param_devices=param_devs, batch_devices=batch_devs,
         single_chip_devices=one_devs, loss_abs_diff=diffs,
         tol=FOUR_CHIP_LOSS_ATOL)
    require(len(param_devs) == 4, f"params on devices {param_devs}")
    require(len(batch_devs) == 4, f"batch on devices {batch_devs}")
    require(len(one_devs) == 1, f"one-chip run on devices {one_devs}")
    require(max(diffs) <= FOUR_CHIP_LOSS_ATOL,
            f"sharded losses {sharded} differ from one chip's {single}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only sharded training on 4 chips against one")
    args = ap.parse_args(argv)

    dev = device_phase(4 if args.four_chips else 1)
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    emit("compile_cache", dir=enable_compile_cache())
    cfg = get_config(ARCH)
    t0 = time.perf_counter()
    if args.four_chips:
        four_chip_phase(dataclasses.replace(cfg, num_layers=4), steps=3,
                        batch=2, seq=4096, seed=args.seed)
    else:
        stats = serve_phase(cfg, batch=8, prompt_len=2048, gen=64,
                            seed=args.seed)
        require(stats["prefill_kernel_calls"] > 0,
                "the prefill program holds no compiled kernel")
        require(stats["decode_kernel_calls"] > 0,
                "the decode program holds no compiled kernel")
        logits_phase(cfg, batch=2, prompt_len=2048, gen=64, seed=args.seed)
        train_phase(dataclasses.replace(cfg, num_layers=8), steps=4,
                    batch=1, seq=4096, seed=args.seed)
    emit("done", wall_s=time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
