"""End-to-end training driver.

Composes every substrate layer: storage-node data pipeline -> sharded
train step -> streaming checkpoints -> straggler detection -> elastic
recovery on injected failures.  Runs on whatever devices exist (CPU for
development, a pod for production).

    PYTHONPATH=src python -m repro.launch.train --arch lovelock-20m \
        --steps 100 --batch 8 --seq 256 --ckpt-dir /tmp/ck
"""
from __future__ import annotations

import argparse
import json
import math
import time

import jax

from repro.configs import get_config, smoke_variant
from repro.core.elastic import StragglerDetector
from repro.core.streaming_checkpoint import StreamingCheckpointer
from repro.data.pipeline import Prefetcher, StorageNodeDataset
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import model as M
from repro.optim import OptimizerConfig, adamw_init
from repro.sharding.rules import ShardingRules, state_specs
from repro.train.steps import make_train_step


def train_loop(cfg, *, steps, batch, seq, ckpt_dir=None, ckpt_every=50,
               lr=3e-4, seed=0, log_every=10, data_mesh=1, model_mesh=1,
               resume=False, log_path=None, use_pallas=False,
               distribution="zipf_markov"):
    """Train `cfg` for `steps` steps on data made from `seed`.

    Returns (state, info).  info["step_times_s"] leaves out the first step
    of the run, which compiles; that one is info["first_step_s"].
    """
    mesh = None
    rules = None
    if data_mesh * model_mesh > 1:
        mesh = make_host_mesh(data_mesh, model_mesh)
        rules = ShardingRules(mesh)
    opt_cfg = OptimizerConfig(lr=lr, warmup=max(10, steps // 20),
                              total_steps=steps)

    def init_state():
        params = M.init_params(jax.random.PRNGKey(seed), cfg, tp=model_mesh)
        return adamw_init(params, opt_cfg)
    shardings = None
    if mesh is not None:
        shardings = rules.to_shardings(
            state_specs(jax.eval_shape(init_state), mesh))
    # one program: every leaf gets a buffer of its own (donation needs
    # that), born on its shards, with no second copy of the state
    state = jax.jit(init_state, out_shardings=shardings)()
    ckpt = StreamingCheckpointer(ckpt_dir) if ckpt_dir else None
    if resume and ckpt and ckpt.latest_step() is not None:
        state = ckpt.restore(jax.eval_shape(lambda: state))
        print(f"resumed from step {int(state.step)}")

    step_fn = jax.jit(make_train_step(cfg, opt_cfg, rules,
                                      use_pallas=use_pallas),
                      donate_argnums=(0,))
    ds = StorageNodeDataset(vocab_size=cfg.vocab_size, seq_len=seq,
                            global_batch=batch, seed=seed,
                            n_storage_nodes=math.gcd(batch, 4),
                            distribution=distribution)
    batch_sharding = rules.batch_sharding() if rules else None
    detector = StragglerDetector(n_hosts=max(jax.process_count(), 1))
    logf = open(log_path, "a") if log_path else None
    losses, step_times, first_step_s = [], [], None
    it = Prefetcher(iter(ds), depth=2,
                    put_fn=lambda b: jax.device_put(b, batch_sharding))
    t_start = time.perf_counter()
    start_step = int(state.step)
    for batch_dev in it:
        step = int(state.step)
        if step >= steps:
            break
        if step < start_step:
            continue
        t0 = time.perf_counter()
        state, metrics = step_fn(state, batch_dev)
        loss = float(metrics["loss"])
        dt = time.perf_counter() - t0
        losses.append(loss)
        rec = {"step": step, "loss": round(loss, 4),
               "step_time_s": round(dt, 3)}
        if first_step_s is None:      # compiles: kept out of the rates
            first_step_s = dt
            rec["compiled"] = True
        else:
            step_times.append(dt)
            detector.observe([dt])
            rec["tokens_per_s"] = round(batch * seq / dt, 1)
        if step % log_every == 0:
            print(json.dumps(rec), flush=True)
            if logf:
                logf.write(json.dumps(rec) + "\n")
                logf.flush()
        if ckpt and (step + 1) % ckpt_every == 0:
            ckpt.save(step + 1, state)
    if ckpt:
        ckpt.save(int(state.step), state)
    wall = time.perf_counter() - t_start
    return state, {"losses": losses, "wall_s": wall,
                   "first_step_s": first_step_s, "step_times_s": step_times,
                   "batch_sharding": batch_dev["tokens"].sharding}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="lovelock-20m")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke variant of --arch")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--data-mesh", type=int, default=1)
    ap.add_argument("--model-mesh", type=int, default=1)
    ap.add_argument("--log", default=None)
    ap.add_argument("--use-pallas", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_variant(cfg)
    state, info = train_loop(
        cfg, steps=args.steps, batch=args.batch, seq=args.seq,
        ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every, lr=args.lr,
        data_mesh=args.data_mesh, model_mesh=args.model_mesh,
        resume=args.resume, log_path=args.log, use_pallas=args.use_pallas)
    l = info["losses"]
    print(f"done: {len(l)} steps, loss {l[0]:.3f} -> {l[-1]:.3f}, "
          f"wall {info['wall_s']:.0f}s")


if __name__ == "__main__":
    main()
