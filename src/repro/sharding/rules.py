"""Logical-axis sharding rules -> PartitionSpec trees.

One rule table covers every architecture: weights are matched by their
key-path in the param pytree; activations by short logical names used in
model code via `rules.cs(x, name)`.

Mesh axes: ("pod",) "data", "model".  Batch/FSDP ride ('pod','data');
tensor/expert parallelism rides 'model'.  For batch=1 long-context decode,
`seq_sharded=True` moves the batch axes onto the sequence dim of the KV
cache instead.
"""
from __future__ import annotations

import re
from typing import Any, Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

Pytree = Any

# (regex on 'path', spec builder taking (batchaxes 'b', 'model')) — first match
# wins. Paths look like "layers/0/attn/wq"; stacked layer leaves have a
# leading n_periods dim handled by `stacked=True` rules (prepend None).
_W = [
    # embeddings
    (r"(embed|lm_head)$",             lambda b: P("model", b)),
    # attention / rwkv projections (D, H, hd) and (H, hd, D)
    (r"(attn|xattn|time_mix)/w[qkvrg]$", lambda b: P(b, "model", None)),
    (r"(attn|xattn|time_mix)/wo$",    lambda b: P("model", None, b)),
    (r"(attn|xattn)/(qn|kn)$",        lambda b: P(None)),
    (r"(attn|xattn)/gate$",           lambda b: P()),
    # MoE
    (r"moe/router$",                  lambda b: P(b, None)),
    (r"moe/(w_gate|w_up)$",           lambda b: P("model", b, None)),
    (r"moe/w_down$",                  lambda b: P("model", None, b)),
    (r"moe/shared/(w_gate|w_up)$",    lambda b: P(b, "model")),
    (r"moe/shared/w_down$",           lambda b: P("model", b)),
    # dense FFN
    (r"(ffn|channel_mix)/(w_gate|w_up|wk)$", lambda b: P(b, "model")),
    (r"(ffn|channel_mix)/(w_down|wv)$", lambda b: P("model", b)),
    (r"channel_mix/wr$",              lambda b: P(b, "model")),
    # mamba
    (r"mamba/in_proj$",               lambda b: P(b, "model")),
    (r"mamba/conv_w$",                lambda b: P(None, "model")),
    (r"mamba/(conv_b|dt_bias|Dskip)$", lambda b: P("model")),
    (r"mamba/x_proj$",                lambda b: P("model", None)),
    (r"mamba/dt_proj$",               lambda b: P(None, "model")),
    (r"mamba/A_log$",                 lambda b: P("model", None)),
    (r"mamba/out_proj$",              lambda b: P("model", b)),
    # rwkv small tensors
    (r"time_mix/w_lora/a$",           lambda b: P(b, None)),
    (r"time_mix/w_lora/b$",           lambda b: P(None, b)),
    (r"time_mix/u$",                  lambda b: P("model", None)),
    # everything else (norm scales, mus, w0, ln_x, ...): shard the feature
    # dim over FSDP when it divides (the fixer below falls back to
    # replicated for small/odd dims — e.g. smoke configs)
    (r".*",                           lambda b: P(b)),
]

def _path_str(path) -> str:
    parts = []
    for e in path:
        if isinstance(e, jax.tree_util.DictKey):
            parts.append(str(e.key))
        elif isinstance(e, jax.tree_util.SequenceKey):
            parts.append(str(e.idx))
        else:
            parts.append(str(e))
    return "/".join(parts)


def param_specs(params: Pytree, mesh, *, stacked_prefix="layers/",
                fsdp_pod: bool = True) -> Pytree:
    """PartitionSpec tree for a param (or optimizer-state) tree, sharded
    (FSDP) over the batch axes."""
    names = mesh.axis_names
    cand = ("pod", "data") if fsdp_pod else ("data",)
    b = tuple(n for n in cand if n in names)
    b = b if len(b) > 1 else (b[0] if b else None)

    def spec_for(path, leaf):
        s = _path_str(path)
        # shared-expert paths contain 'moe/shared/...' — ensure the
        # shared rules fire before generic moe rules via ordering above.
        for pat, fn in _W:
            m = re.search(pat, s)
            if m:
                spec = fn(b)
                break
        # stacked layer leaves carry a leading n_periods dim
        if s.startswith(stacked_prefix) or "/layers/" in s:
            spec = P(None, *spec)
        if len(spec) > leaf.ndim:
            spec = P(*spec[:leaf.ndim])
        if len(spec) < leaf.ndim:
            spec = P(*(tuple(spec) + (None,) * (leaf.ndim - len(spec))))
        # drop axes that do not divide
        fixed = []
        for dim, ax in zip(leaf.shape, spec):
            size = 1
            for a in (ax if isinstance(ax, tuple) else
                      ((ax,) if ax else ())):
                size *= mesh.shape[a]
            fixed.append(ax if size and dim % max(size, 1) == 0 else None)
        return P(*fixed)

    return jax.tree_util.tree_map_with_path(spec_for, params)


def state_specs(state, mesh, *, fsdp_pod: bool = True) -> Any:
    """PartitionSpec tree for a TrainState (params/m/v/master/ef).

    int8 moment leaves are {"q": like-param, "scale": like-param[:-1]}.
    """
    pspecs = param_specs(state.params, mesh, fsdp_pod=fsdp_pod)

    def moment_spec(ps, leaf):
        if isinstance(leaf, dict):      # int8 {"q","scale"}
            return {"q": ps, "scale": P(*tuple(ps)[:-1])}
        return ps

    def like_params(tree):
        if tree is None:
            return None
        flat_ps = jax.tree.leaves(pspecs,
                                  is_leaf=lambda x: isinstance(x, P))
        tdef = jax.tree.structure(state.params)
        leaves = tdef.flatten_up_to(tree)
        return jax.tree.unflatten(tdef, [moment_spec(ps, lf)
                                         for ps, lf in zip(flat_ps, leaves)])

    return type(state)(
        step=P(),
        params=pspecs,
        m=like_params(state.m),
        v=like_params(state.v),
        master=like_params(state.master),
        ef=like_params(state.ef),
    )


class ShardingRules:
    """Activation constraints + input/cache/param shardings for one run."""

    def __init__(self, mesh, *, seq_sharded: bool = False, batch: int = 0,
                 exclude_pod: bool = False):
        self.mesh = mesh
        names = mesh.axis_names
        cand = ("data",) if exclude_pod else ("pod", "data")
        bd = tuple(n for n in cand if n in names)
        bsize = 1
        for n in bd:
            bsize *= mesh.shape[n]
        self.batch_axes = bd if len(bd) > 1 else (bd[0] if bd else None)
        self.seq_sharded = seq_sharded
        ba = self.batch_axes
        if seq_sharded:     # batch=1 long-context: seq carries the DP axes
            B, S = None, ba
        else:
            B, S = ba, None
        self.table = {
            "act_bsd":   P(B, S, None),
            "act_bshd":  P(B, S, "model", None),
            "act_bsf":   P(B, S, "model"),
            "logits_bsv": P(B, S, "model"),
            "tokens":    P(B, S),
        }

    def ns(self, name: str) -> NamedSharding:
        return NamedSharding(self.mesh, self.table[name])

    def cs(self, x, name: str):
        spec = self.table[name]
        # drop non-dividing axes (e.g. batch 1, tiny head counts in smoke)
        fixed = []
        for dim, ax in zip(x.shape, spec):
            size = 1
            for a in (ax if isinstance(ax, tuple) else
                      ((ax,) if ax else ())):
                size *= self.mesh.shape[a]
            fixed.append(ax if dim % max(size, 1) == 0 else None)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, P(*fixed)))

    # ---- input/cache sharding trees (for jit in_shardings) ----

    def batch_sharding(self) -> NamedSharding:
        return self.ns("tokens")

    def cache_specs(self, caches: Pytree) -> Pytree:
        B, S = ((None, self.batch_axes) if self.seq_sharded
                else (self.batch_axes, None))

        def spec_for(path, leaf):
            s = _path_str(path)
            if leaf.ndim == 0:
                return P()
            if s.endswith("/kv/k") or s.endswith("/kv/v"):
                # self-attention ring (n_periods, B, Kp, hd, Wp)
                spec = P(None, B, "model", None, S)
            elif s.endswith("/k") or s.endswith("/v"):
                # cross-attention source (n_periods, B, T, Kp, hd)
                spec = P(None, B, S, "model", None)
            elif "mamba/conv" in s:
                spec = P(None, B, None, "model")
            elif "mamba/ssm" in s:
                spec = P(None, B, "model", None)
            elif "tm/wkv" in s:
                spec = P(None, B, "model", None, None)
            elif s.endswith("shift") or s.endswith("cm"):
                spec = P(None, B, None, None)
            else:
                spec = P(*([None] * leaf.ndim))
            spec = P(*spec[:leaf.ndim])
            fixed = []
            for dim, ax in zip(leaf.shape, spec):
                size = 1
                for a in (ax if isinstance(ax, tuple) else
                          ((ax,) if ax else ())):
                    size *= self.mesh.shape[a]
                fixed.append(ax if dim % max(size, 1) == 0 else None)
            return P(*fixed)

        return jax.tree_util.tree_map_with_path(spec_for, caches)

    def to_shardings(self, spec_tree: Pytree) -> Pytree:
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s), spec_tree,
                            is_leaf=lambda x: isinstance(x, P))
