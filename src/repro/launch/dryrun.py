import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("_DRYRUN_EXTRA_XLA", "") +
                           " --xla_force_host_platform_device_count=512")

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The lines above MUST precede any jax import (jax locks the platform and
the device count at first init).  The dry-run compiles for 512 fake host
devices on the CPU, so it never takes an accelerator, even where one is
attached.  Usage:

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-32b \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both

Each successful cell writes artifacts/dryrun/<arch>__<shape>__<mesh>.json
with per-device FLOPs/bytes, collective bytes by tier (ICI vs DCN), peak
memory, the derived roofline terms, and a `sim_trace` block that
`repro.sim.workloads.training_from_trace` replays.
"""
import argparse
import json
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, ALL_ARCHS, get_config, supports_shape
from repro.launch.hlo_analysis import Roofline, model_flops_per_step
from repro.launch.hlo_cost import analyze as hlo_analyze
from repro.launch.mesh import make_production_mesh
from repro.optim.adamw import OptimizerConfig
from repro.sharding.rules import ShardingRules, param_specs, state_specs
from repro.train.steps import (
    abstract_caches, abstract_state, input_specs, make_serve_step,
    make_prefill, make_train_step)

ART = pathlib.Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"

# archs whose optimizer state must be int8 to have any chance of fitting
# a 256-chip pod (Lovelock's bounded-memory ethos)
_INT8_STATE = {"kimi-k2-1t-a32b", "llama3-405b", "llama-3.2-vision-90b"}


def _batch_shardings(batch, rules):
    def spec(path, leaf):
        if leaf.ndim >= 3:        # stub frontend embeddings (B, T, D)
            sp = P(rules.batch_axes, None, None)
        else:
            sp = rules.table["tokens"]
        fixed = []
        for dim, ax in zip(leaf.shape, sp):
            size = 1
            for a in (ax if isinstance(ax, tuple) else ((ax,) if ax else ())):
                size *= rules.mesh.shape[a]
            fixed.append(ax if dim % max(size, 1) == 0 else None)
        return NamedSharding(rules.mesh, P(*fixed))
    return jax.tree_util.tree_map_with_path(spec, batch)


def lower_cell(arch: str, shape_name: str, mesh, *, grad_sync="gspmd",
               remat=True, compute_dtype=None):
    """Lower one cell; returns (lowered, aux_info)."""
    import dataclasses
    cfg = get_config(arch)
    if compute_dtype:
        cfg = dataclasses.replace(cfg, compute_dtype=compute_dtype)
    shape = SHAPES[shape_name]
    tp = mesh.shape["model"]
    dp = 1
    for n in ("pod", "data"):
        if n in mesh.axis_names:
            dp *= mesh.shape[n]
    seq_sharded = (shape.kind == "decode" and shape.global_batch < dp)
    rules = ShardingRules(mesh, seq_sharded=seq_sharded)
    opt_cfg = OptimizerConfig(
        state_dtype="int8" if arch in _INT8_STATE else "float32",
        master=arch not in _INT8_STATE)

    with mesh:
        if shape.kind == "train":
            state = abstract_state(cfg, opt_cfg, tp,
                                   with_ef=(grad_sync == "compressed_pod"))
            sspec = state_specs(state, mesh,
                                fsdp_pod=(grad_sync != "compressed_pod"))
            sshard = jax.tree.map(
                lambda s: NamedSharding(mesh, s), sspec,
                is_leaf=lambda x: isinstance(x, P))
            batch = input_specs(cfg, shape)
            bshard = _batch_shardings(batch, rules)
            step = make_train_step(cfg, opt_cfg, rules, remat=remat,
                                   grad_sync=grad_sync)
            lowered = jax.jit(step, in_shardings=(sshard, bshard),
                              out_shardings=(sshard, None)).lower(state, batch)
        elif shape.kind == "prefill":
            from repro.models import model as M
            params = jax.eval_shape(
                lambda: M.init_params(jax.random.PRNGKey(0), cfg, tp))
            pshard = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                  param_specs(params, mesh),
                                  is_leaf=lambda x: isinstance(x, P))
            caches = abstract_caches(cfg, shape, tp)
            cshard = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                  rules.cache_specs(caches),
                                  is_leaf=lambda x: isinstance(x, P))
            batch = input_specs(cfg, shape)
            bshard = _batch_shardings(batch, rules)
            fn = make_prefill(cfg, rules)
            lowered = jax.jit(fn, in_shardings=(pshard, cshard, bshard),
                              out_shardings=(None, cshard)).lower(
                params, caches, batch)
        else:  # decode
            from repro.models import model as M
            params = jax.eval_shape(
                lambda: M.init_params(jax.random.PRNGKey(0), cfg, tp))
            pshard = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                  param_specs(params, mesh),
                                  is_leaf=lambda x: isinstance(x, P))
            caches = abstract_caches(cfg, shape, tp)
            cshard = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                  rules.cache_specs(caches),
                                  is_leaf=lambda x: isinstance(x, P))
            tok = input_specs(cfg, shape)["token"]
            tshard = _batch_shardings({"token": tok}, rules)["token"]
            fn = make_serve_step(cfg, rules)
            lowered = jax.jit(fn, in_shardings=(pshard, cshard, tshard),
                              out_shardings=(tshard, cshard)).lower(
                params, caches, tok)
    return lowered, {"cfg": cfg, "shape": shape, "rules": rules}


def run_cell(arch: str, shape_name: str, mesh_kind: str, *,
             grad_sync="gspmd", remat=True, save=True, tag="",
             compute_dtype=None) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = supports_shape(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                "status": "skipped", "reason": why}
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_dev = mesh.size
    pod_size = (n_dev // mesh.shape["pod"]) if "pod" in mesh.axis_names \
        else None
    t0 = time.perf_counter()
    lowered, aux = lower_cell(arch, shape_name, mesh, grad_sync=grad_sync,
                              remat=remat, compute_dtype=compute_dtype)
    t1 = time.perf_counter()
    compiled = lowered.compile()
    t2 = time.perf_counter()
    mem = compiled.memory_analysis()
    # trip-count-aware HLO costs (XLA's cost_analysis counts scan bodies
    # once — see hlo_cost.py)
    cost = hlo_analyze(compiled.as_text(), pod_size=pod_size)
    mf = model_flops_per_step(cfg, shape) / n_dev
    roof = Roofline(flops=cost.flops, hbm_bytes=cost.bytes,
                    ici_bytes=cost.coll_ici, dcn_bytes=cost.coll_dcn,
                    model_flops=mf)
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "status": "ok", "grad_sync": grad_sync, "tag": tag,
        "n_devices": n_dev,
        "lower_s": round(t1 - t0, 1), "compile_s": round(t2 - t1, 1),
        "bytes_per_device": getattr(mem, "temp_size_in_bytes", 0)
        + getattr(mem, "argument_size_in_bytes", 0),
        "peak_temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
        "arg_bytes": getattr(mem, "argument_size_in_bytes", 0),
        "output_bytes": getattr(mem, "output_size_in_bytes", 0),
        "collectives": {"by_kind": cost.coll_by_kind,
                        "ici_bytes": cost.coll_ici,
                        "dcn_bytes": cost.coll_dcn,
                        "n_ops": cost.n_coll_ops},
        "scan_trip_counts": cost.trip_counts,
        "roofline": roof.to_dict(),
        # consumed by repro.sim.workloads.training_from_trace
        "sim_trace": {
            "n_devices": n_dev,
            "phases": [
                {"kind": "compute", "flops": cost.flops,
                 "hbm_bytes": cost.bytes},
                {"kind": "collective_phase", "tier": "ici",
                 "bytes": cost.coll_ici},
                {"kind": "collective_phase", "tier": "dcn",
                 "bytes": cost.coll_dcn},
            ],
        },
    }
    if save:
        ART.mkdir(parents=True, exist_ok=True)
        name = f"{arch}__{shape_name}__{mesh_kind}"
        if tag:
            name += f"__{tag}"
        (ART / f"{name}.json").write_text(json.dumps(rec, indent=1))
        import gzip
        hlo_dir = ART.parent / "hlo"
        hlo_dir.mkdir(parents=True, exist_ok=True)
        with gzip.open(hlo_dir / f"{name}.txt.gz", "wt") as f:
            f.write(compiled.as_text())
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi",
                                                         "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--grad-sync", default="gspmd")
    ap.add_argument("--tag", default="")
    ap.add_argument("--compute-dtype", default=None)
    ap.add_argument("--no-remat", action="store_true")
    args = ap.parse_args()

    cells = []
    archs = ALL_ARCHS if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    fails = 0
    for arch in archs:
        for shape in shapes:
            for mk in meshes:
                try:
                    rec = run_cell(arch, shape, mk,
                                   grad_sync=args.grad_sync, tag=args.tag,
                                   remat=not args.no_remat,
                                   compute_dtype=args.compute_dtype)
                except Exception as e:  # noqa: BLE001
                    rec = {"arch": arch, "shape": shape, "mesh": mk,
                           "status": "fail", "error": f"{type(e).__name__}: {e}"}
                    fails += 1
                cells.append(rec)
                r = rec.get("roofline", {})
                print(f"[{rec['status']:7s}] {arch:24s} {shape:12s} {mk:6s} "
                      f"compile={rec.get('compile_s', '-')}s "
                      f"bottleneck={r.get('bottleneck', '-')} "
                      f"roof={r.get('roofline_fraction', 0):.3f} "
                      f"{rec.get('reason', rec.get('error', ''))}"[:200],
                      flush=True)
    print(f"\n{sum(1 for c in cells if c['status']=='ok')} ok, "
          f"{sum(1 for c in cells if c['status']=='skipped')} skipped, "
          f"{fails} failed")
    sys.exit(1 if fails else 0)


if __name__ == "__main__":
    main()
