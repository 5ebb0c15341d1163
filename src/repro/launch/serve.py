"""Batched serving driver: prefill a prompt batch, decode N tokens.

    PYTHONPATH=src python -m repro.launch.serve --arch lovelock-20m \
        --batch 4 --prompt-len 64 --gen 32
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config, smoke_variant
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as M
from repro.train.steps import make_prefill, make_serve_step


def kernel_calls(compiled) -> int:
    """Pallas kernels compiled into a program (0 where they interpret)."""
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def serve(cfg, *, batch, prompt_len, gen, seed=0, use_pallas=False):
    """Prefill `batch` random prompts made from `seed`, greedy-decode `gen`.

    Prefill and the decode step are compiled ahead of time, outside the
    timed windows, and the caches are donated to both, so the device holds
    one copy of them.
    """
    params = M.init_params(jax.random.PRNGKey(seed), cfg, tp=1)
    caches = M.init_caches(cfg, batch, prompt_len + gen, tp=1)
    prompts = jax.random.randint(jax.random.PRNGKey(seed + 1),
                                 (batch, prompt_len), 0, cfg.vocab_size)
    inputs = {"tokens": prompts}
    extra = {}
    if cfg.cross_attn_every:
        extra["image_embeds"] = jnp.zeros(
            (batch, cfg.num_image_tokens, cfg.d_model), jnp.bfloat16)
    if cfg.encoder_layers:
        extra["audio_frames"] = jnp.zeros(
            (batch, cfg.num_audio_frames, cfg.d_model), jnp.bfloat16)
    if extra:
        inputs["extra"] = extra

    t0 = time.perf_counter()
    prefill = jax.jit(make_prefill(cfg, use_pallas=use_pallas),
                      donate_argnums=(1,)).lower(
                          params, caches, inputs).compile()
    step = jax.jit(make_serve_step(cfg, use_pallas=use_pallas),
                   donate_argnums=(1,)).lower(
                       params, caches,
                       jax.ShapeDtypeStruct((batch, 1), jnp.int32)).compile()
    t_compile = time.perf_counter() - t0

    t0 = time.perf_counter()
    logits, caches = prefill(params, caches, inputs)
    jax.block_until_ready(logits)
    t_prefill = time.perf_counter() - t0
    tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]

    out = [tok]
    jax.block_until_ready(tok)
    t0 = time.perf_counter()
    for _ in range(gen - 1):
        tok, caches = step(params, caches, tok)
        out.append(tok)
    jax.block_until_ready(out[-1])
    t_decode = time.perf_counter() - t0
    gen_tokens = jnp.concatenate(out, axis=1)
    return gen_tokens, {
        "compile_s": t_compile,
        "prefill_s": t_prefill,
        "prefill_tokens_per_s": batch * prompt_len / t_prefill,
        "decode_s": t_decode,
        "decode_tokens_per_s": batch * (gen - 1) / max(t_decode, 1e-9),
        "prefill_kernel_calls": kernel_calls(prefill),
        "decode_kernel_calls": kernel_calls(step),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="lovelock-20m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--use-pallas", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_variant(cfg)
    toks, stats = serve(cfg, batch=args.batch, prompt_len=args.prompt_len,
                        gen=args.gen, use_pallas=args.use_pallas)
    print("generated shape:", toks.shape)
    for k, v in stats.items():
        print(f"  {k}: {v:.6g}")


if __name__ == "__main__":
    main()
