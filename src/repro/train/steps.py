"""Train / serve step builders and abstract input specs.

`input_specs(cfg, shape, mesh)` produces jax.ShapeDtypeStruct stand-ins for
every model input — weak-type-correct, shardable, no device allocation —
used by the multi-pod dry-run and the roofline harness.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import model as M
from repro.optim.adamw import OptimizerConfig, TrainState, adamw_update
from repro.optim.schedules import cosine_schedule
from repro.sharding.rules import ShardingRules

Pytree = Any


def cross_entropy(logits, labels, vocab_size: int):
    """Mean CE over tokens; padded vocab tail masked out."""
    logits = logits.astype(jnp.float32)
    Vp = logits.shape[-1]
    if Vp != vocab_size:
        neg = jnp.where(jnp.arange(Vp) < vocab_size, 0.0, -1e30)
        logits = logits + neg
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None],
                               axis=-1).squeeze(-1)
    return jnp.mean(logz - gold)


def make_train_step(cfg: ModelConfig, opt_cfg: OptimizerConfig,
                    rules: Optional[ShardingRules] = None, *,
                    use_pallas: bool = False, remat: bool = True,
                    grad_sync: str = "gspmd", microbatches: int = 1):
    """Returns train_step(state, batch) -> (state, metrics).

    grad_sync: 'gspmd' (XLA-inserted collectives) or 'compressed_pod'
    (Lovelock §6: explicit int8 error-feedback all-reduce on the cross-pod
    hop via shard_map — see core/collectives.py).

    microbatches > 1: gradient accumulation over k sequential microbatches
    (fp32 accumulator) — per-step activation residency drops ~k x, the
    key knob for fitting large global batches in HBM.
    """
    lr_fn = cosine_schedule(opt_cfg.lr, opt_cfg.warmup, opt_cfg.total_steps)

    def loss_fn(params, batch):
        logits, aux, _ = M.forward(params, cfg, batch["tokens"],
                                   extra=batch.get("extra"), rules=rules,
                                   use_pallas=use_pallas, remat=remat)
        ce = cross_entropy(logits, batch["labels"], cfg.vocab_size)
        return ce + aux, {"loss": ce, "aux": aux}

    def _grads(params, batch):
        if microbatches <= 1:
            return jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
        k = microbatches

        def split(x):
            return x.reshape((k, x.shape[0] // k) + x.shape[1:])
        mbs = jax.tree.map(split, batch)

        def body(acc, mb):
            (_, metrics), g = jax.value_and_grad(
                loss_fn, has_aux=True)(params, mb)
            acc = jax.tree.map(
                lambda a, x: a + x.astype(jnp.float32), acc, g)
            return acc, metrics
        acc0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                            params)
        acc, ms = jax.lax.scan(body, acc0, mbs)
        grads = jax.tree.map(lambda a: a / k, acc)
        metrics = jax.tree.map(lambda m: jnp.mean(m), ms)
        return (None, metrics), grads

    def train_step(state: TrainState, batch) -> tuple[TrainState, dict]:
        (_, metrics), grads = _grads(state.params, batch)
        new_state = adamw_update(state, grads, opt_cfg, lr_fn)
        return new_state, metrics

    if grad_sync != "compressed_pod" or rules is None or \
            "pod" not in rules.mesh.axis_names:
        return train_step

    # Lovelock compressed cross-pod sync: the whole step runs manual over
    # 'pod' (auto over data/model); gradients cross DCN as int8+EF.
    from jax.sharding import PartitionSpec as P
    from repro.core.collectives import compressed_pod_sync
    mesh = rules.mesh
    # NOTE: with_sharding_constraint inside a partial-manual shard_map
    # trips an XLA SPMD-partitioner check (spmd_partitioner_util.cc:504 in
    # XLA as of jax 0.8) — so the inner forward runs without activation
    # constraints; GSPMD propagates layouts from the (auto-axis) param
    # shardings instead.
    inner_rules = None

    def inner_loss(params, batch):
        logits, aux, _ = M.forward(params, cfg, batch["tokens"],
                                   extra=batch.get("extra"),
                                   rules=inner_rules,
                                   use_pallas=use_pallas, remat=remat)
        ce = cross_entropy(logits, batch["labels"], cfg.vocab_size)
        return ce + aux, {"loss": ce, "aux": aux}

    def inner(state: TrainState, batch):
        (_, metrics), grads = jax.value_and_grad(
            inner_loss, has_aux=True)(state.params, batch)
        grads, new_ef = compressed_pod_sync(grads, state.ef, mesh)
        state = state._replace(ef=new_ef)
        new_state = adamw_update(state, grads, opt_cfg, lr_fn)
        metrics = jax.tree.map(lambda m: jax.lax.pmean(m, "pod"), metrics)
        return new_state, metrics

    def make_specs(state, batch):
        sspec = jax.tree.map(lambda _: P(), state)
        bspec = jax.tree.map(lambda _: P("pod"), batch)
        return sspec, bspec

    def wrapped(state, batch):
        sspec, bspec = make_specs(state, batch)
        return jax.shard_map(inner, mesh=mesh, in_specs=(sspec, bspec),
                             out_specs=(sspec, jax.tree.map(
                                 lambda _: P(), {"loss": 0, "aux": 0})),
                             axis_names={"pod"}, check_vma=False)(state, batch)
    return wrapped


def make_prefill(cfg: ModelConfig, rules=None, *, use_pallas=False):
    def prefill(params, caches, batch):
        logits, _, caches = M.forward(params, cfg, batch["tokens"],
                                      extra=batch.get("extra"), rules=rules,
                                      caches=caches, use_pallas=use_pallas,
                                      remat=False)
        return logits[:, -1:], caches
    return prefill


def make_serve_step(cfg: ModelConfig, rules=None, *, use_pallas=False,
                    sample: str = "greedy"):
    def serve_step(params, caches, token):
        logits, caches = M.decode_step(params, cfg, token, caches,
                                       rules=rules, use_pallas=use_pallas)
        with jax.named_scope("sample"):
            if sample == "greedy":
                Vp = logits.shape[-1]
                if Vp != cfg.vocab_size:
                    logits = logits + jnp.where(
                        jnp.arange(Vp) < cfg.vocab_size, 0.0, -1e30)
                nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            else:
                nxt = token[:, -1]
            return nxt[:, None], caches
    return serve_step


# ---------------------------------------------------------------------------
# Abstract inputs (dry-run / roofline)
# ---------------------------------------------------------------------------


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    """ShapeDtypeStructs for the *data* inputs of a step (no allocation)."""
    B, S = shape.global_batch, shape.seq_len
    sd = jax.ShapeDtypeStruct
    dt = jnp.dtype(cfg.compute_dtype)
    if shape.kind == "train":
        batch = {"tokens": sd((B, S), jnp.int32),
                 "labels": sd((B, S), jnp.int32)}
        extra = {}
        if cfg.cross_attn_every:
            extra["image_embeds"] = sd((B, cfg.num_image_tokens,
                                        cfg.d_model), dt)
        if cfg.encoder_layers:
            extra["audio_frames"] = sd((B, cfg.num_audio_frames,
                                        cfg.d_model), dt)
        if extra:
            batch["extra"] = extra
        return batch
    if shape.kind == "prefill":
        batch = {"tokens": sd((B, S), jnp.int32)}
        extra = {}
        if cfg.cross_attn_every:
            extra["image_embeds"] = sd((B, cfg.num_image_tokens,
                                        cfg.d_model), dt)
        if cfg.encoder_layers:
            extra["audio_frames"] = sd((B, cfg.num_audio_frames,
                                        cfg.d_model), dt)
        if extra:
            batch["extra"] = extra
        return batch
    # decode: one new token against a seq_len-deep KV cache
    return {"token": sd((B, 1), jnp.int32)}


def abstract_caches(cfg: ModelConfig, shape: ShapeConfig, tp: int,
                    dtype=jnp.bfloat16) -> Pytree:
    """ShapeDtypeStruct tree matching init_caches (no allocation)."""
    caches = jax.eval_shape(
        functools.partial(M.init_caches, cfg, shape.global_batch,
                          shape.seq_len, tp, dtype))
    return caches


def abstract_state(cfg: ModelConfig, opt_cfg: OptimizerConfig, tp: int,
                   with_ef: bool = False) -> Pytree:
    """ShapeDtypeStruct tree for the full TrainState (no allocation)."""
    from repro.optim.adamw import adamw_init

    def build():
        params = M.init_params(jax.random.PRNGKey(0), cfg, tp)
        return adamw_init(params, opt_cfg, with_ef=with_ef)
    return jax.eval_shape(build)
