"""The optimized HLO of a chip cell's serving programs, compiled for a
described TPU v5e (no chip needed), with what is only metadata taken out, so
that the programs of two source trees can be compared with `cmp`:

    python benchmarks/hlo_text.py --workload danube-decode --out a.txt
    python benchmarks/hlo_text.py --workload danube-decode \\
        --src <other tree>/src --out b.txt
    cmp a.txt b.txt

`prefill` and `serve_step` are built as `benchmarks/chip/kinds/
serve_static.py` builds them (the cell's configuration, batch, prompt length
and cache, the Pallas kernels compiled, the caches donated).  Taken out:
each op's `metadata={...}` (its `jax.named_scope` path and source line),
the stack-frame tables, the numbers and `.clone`s XLA appends to
instruction names (`%name.12.clone` becomes `%name#k`, k its order of first
appearance), and the source locations inside each Pallas kernel's
serialized Mosaic module (the module stands as the SHA-1 of its text
without them).
"""
from __future__ import annotations

import argparse
import base64
import hashlib
import importlib
import os
import pathlib
import re
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def _kernel_text(m) -> str:
    """A kernel's serialized Mosaic module -> SHA-1 of its text without
    source locations.  A module that the compiler itself wrote (XLA's
    own kernels, such as the ragged dot's) comes as text, not bytecode."""
    from jax._src.lib.mlir import ir
    body = base64.b64decode(m.group(2))
    if not body.startswith(b"ML\xefR"):
        text = re.sub(r"\s*loc\([^)]*\)", "", body.decode())
        return m.group(1) + hashlib.sha1(text.encode()).hexdigest()
    with ir.Context() as ctx:
        ctx.allow_unregistered_dialects = True
        module = ir.Module.parse(body)
        text = module.operation.get_asm(enable_debug_info=False)
    return m.group(1) + hashlib.sha1(text.encode()).hexdigest()


def canonical(hlo: str) -> str:
    """HLO text without metadata, stack-frame tables, name numbers or the
    source locations inside kernels."""
    hlo = re.sub(r",? metadata=\{[^}]*\}", "", hlo)
    hlo = re.sub(r'("body":\s*")([A-Za-z0-9+/=]+)', _kernel_text, hlo)
    out, skip = [], False
    for line in hlo.splitlines():
        if line in _TABLES:
            skip = True
        elif skip and not line.startswith(" ") and not line[:1].isdigit():
            skip = False
        if not skip:
            out.append(line)
    ids: dict = {}

    def rename(m):
        base = re.sub(r"(\.(\d+|clone))+$", "", m.group(0))
        return f"{base}#{ids.setdefault(m.group(0), len(ids))}"
    return "\n".join(re.sub(r"%[A-Za-z_][\w.\-]*", rename, line)
                     for line in out) + "\n"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--src", default=str(ROOT / "src"),
                    help="the program's source tree (default: this one)")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(pathlib.Path(args.src).resolve()),
                    str(ROOT / "benchmarks" / "chip")]
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import run
    from repro.models import model as M
    from repro.train.steps import make_prefill, make_serve_step
    # the wrappers would interpret the kernels on the CPU backend; a tree
    # may lack a kernel that a later one has
    for name in ("decode_attention", "flash_attention", "selective_scan"):
        try:
            mod = importlib.import_module(f"repro.kernels.{name}.ops")
        except ModuleNotFoundError:
            continue
        mod.interpret_mode = lambda i=None: False if i is None else i
    jax.config.update("jax_enable_compilation_cache", False)

    bench = run.load_json(ROOT / "BENCHMARK.json")
    cell = run.cell_of(bench, args.workload)
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    c = run.load_json(ROOT / conf["file"])
    traffic = run.load_json(ROOT / "benchmarks" / "chip" / "traffic"
                            / f"{cell['traffic']}.json")
    kind = run.load_module(ROOT / "benchmarks" / "chip" / "kinds"
                           / f"{traffic['kind']}.py", "kind")
    # a hybrid kind runs serve_static's loop over a model of its own
    bm = kind.hybrid.model if hasattr(kind, "hybrid") else run.load_module(
        ROOT / "benchmarks" / "chip" / "model.py", "bench_model")
    B, P = traffic["batch"], traffic["prompt_len"]
    G = int(getattr(kind, "static", kind).answer_lengths(traffic).max())
    cfg = bm.program_config(c)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def sds(t):
        return jax.ShapeDtypeStruct(t.shape, t.dtype, sharding=chip)
    params = jax.tree.map(sds, jax.eval_shape(lambda: bm.make_weights(c, 0)))
    caches = jax.tree.map(sds, jax.eval_shape(
        lambda: M.init_caches(cfg, B, P + G, tp=1)))
    prefill = jax.jit(make_prefill(cfg, use_pallas=True),
                      donate_argnums=(1,)).lower(
        params, caches, {"tokens": sds(jax.ShapeDtypeStruct((B, P),
                                                            jnp.int32))})
    step = jax.jit(make_serve_step(cfg, use_pallas=True),
                   donate_argnums=(1,)).lower(
        params, caches, sds(jax.ShapeDtypeStruct((B, 1), jnp.int32)))
    text = "".join(canonical(low.compile().as_text())
                   for low in (prefill, step))
    pathlib.Path(args.out).write_text(text)
    print(f"{args.out}: {text.count(chr(10))} lines, the program of "
          f"{args.src}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
