"""Time the flash-attention forward alone on one TPU, at the prefill shapes of
the chip cells, and check it against the jnp reference there:

    python benchmarks/flash_time.py [--src <tree>/src] [--sweep]

Prints one JSON line per measurement: the whole op (`ops.flash_attention`:
pads, transposes, kernel, slice-back) in ms per call, the median of
`--reps` timed batches of `--calls` calls, and its largest gap to the
reference; with `--sweep`, `flash_attention_fwd` (the kernel and its own
transposes, on inputs already padded) at each (bq, bk) and padded or true
head size.  `--src` times another source tree's kernel, whose
`flash_attention_fwd` takes `bq` and `bk` as keywords too.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import statistics
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]

# cell: (B, S, H, K, d, window), as each cell's prefill calls the kernel
CELLS = {
    "danube-decode": (32, 1020, 32, 8, 80, 4096),
    "deepseek-codecomp": (8, 1500, 56, 8, 128, None),
    "jamba-chat": (8, 1020, 32, 8, 128, None),
}


def per_call_ms(fn, args, calls, reps):
    import jax
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(reps):
        t = time.perf_counter()
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t) / calls * 1e3)
    return statistics.median(times)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--src", default=str(ROOT / "src"))
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--reps", type=int, default=5)
    a = ap.parse_args()
    sys.path.insert(0, a.src)
    import jax
    import jax.numpy as jnp
    from repro.kernels.flash_attention import ops
    from repro.kernels.flash_attention.flash_attention import \
        flash_attention_fwd
    from repro.kernels.flash_attention.ref import flash_attention_ref

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"no TPU: {dev.platform}")
    for cell, (B, S, H, K, d, w) in CELLS.items():
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (B, S, H, d), jnp.bfloat16)
        k = jax.random.normal(ks[1], (B, S, K, d), jnp.bfloat16)
        v = jax.random.normal(ks[2], (B, S, K, d), jnp.bfloat16)
        op = jax.jit(lambda q, k, v, w=w: ops.flash_attention(
            q, k, v, causal=True, window=w))
        ref = jax.jit(lambda q, k, v, w=w: flash_attention_ref(
            q.astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32), causal=True, window=w))
        two = (q[:2], k[:2], v[:2])      # the reference's scores are big
        gap = float(jnp.max(jnp.abs(op(*two).astype(jnp.float32)
                                    - ref(*two))))
        print(json.dumps({"cell": cell, "op_ms": per_call_ms(
            op, (q, k, v), a.calls, a.reps), "max_gap": gap,
            "src": a.src, "device": dev.device_kind}), flush=True)
        if not a.sweep:
            continue
        Sp = -(-S // 512) * 512
        for dp in sorted({d, 128}):
            pad = ((0, 0), (0, Sp - S), (0, 0), (0, dp - d))
            qp, kp, vp = (jnp.pad(x, pad) for x in (q, k, v))
            for bq in (128, 256, 512):
                for bk in (128, 256, 512):
                    fn = jax.jit(lambda q, k, v, bq=bq, bk=bk, w=w:
                                 flash_attention_fwd(
                                     q, k, v, bq=bq, bk=bk, causal=True,
                                     window=w, seq_len=S,
                                     scale=1.0 / d ** 0.5))
                    row = {"cell": cell, "bq": bq, "bk": bk, "d": dp}
                    try:
                        row["kernel_ms"] = per_call_ms(fn, (qp, kp, vp),
                                                       a.calls, a.reps)
                    except Exception as e:   # tiles the chip refuses
                        row["error"] = str(e).splitlines()[0][:200]
                    print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
