"""Production training driver.

On real hardware: ``python -m repro.launch.train --arch qwen2-72b
--shape train_4k --mesh production`` inside a jax.distributed-initialized
pod job.  On this CPU container: ``--mesh host --smoke`` trains the reduced
config end-to-end with the same code path (sharding rules, fault-tolerant
loop, checkpointing).
"""

import argparse
import dataclasses
import functools
import logging
import time

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.configs import SHAPES, get_config
from repro.distributed import sharding as shlib
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import lm
from repro.train import (OptConfig, data, fault_tolerance as ft,
                         init_opt_state, make_train_step)

log = logging.getLogger("repro.launch.train")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--mesh", choices=["host", "production", "production-multi"],
                    default="host")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=0)
    ap.add_argument("--global-batch", type=int, default=0)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    logging.basicConfig(level=logging.INFO)
    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    shape = SHAPES[args.shape]
    seq = args.seq_len or (64 if args.smoke else shape.seq_len)
    gbs = args.global_batch or (8 if args.smoke else shape.global_batch)

    if args.mesh == "host":
        mesh = make_host_mesh()
    else:
        mesh = make_production_mesh(multi_pod=args.mesh.endswith("multi"))
    ctx = shlib.make_ctx(mesh)
    shlib.set_sharding_ctx(ctx)
    log.info("mesh %s axes %s | arch %s (%.2fB params)", mesh.shape,
             mesh.axis_names, cfg.name, cfg.param_count() / 1e9)

    opt_cfg = OptConfig(lr=args.lr, total_steps=args.steps,
                        warmup_steps=max(1, args.steps // 20))
    step = make_train_step(cfg, opt_cfg, num_microbatches=args.microbatches,
                           loss_chunk=min(1024, seq))

    def init_fn():
        params = lm.init_params(cfg, jax.random.PRNGKey(0))
        return {"params": params, "opt": init_opt_state(params)}

    with jax.set_mesh(mesh):
        params_specs = shlib.param_specs(jax.eval_shape(init_fn)["params"], ctx)
        shardings = {"params": shlib.named_shardings(params_specs, mesh),
                     "opt": None}
        fcfg = ft.FaultConfig(ckpt_dir=args.ckpt_dir or f"/tmp/ckpt_{cfg.name}",
                              ckpt_every=args.ckpt_every)
        state, extra, start = ft.resume_or_init(fcfg, init_fn)
        pipe = data.make_pipeline(cfg, type("S", (), {
            "seq_len": seq, "global_batch": gbs})(),
            process_index=jax.process_index(),
            process_count=jax.process_count())
        if extra.get("data"):
            pipe.restore(extra["data"])

        jstep = jax.jit(step, donate_argnums=(0, 1))
        t0 = time.time()

        def step_fn(state, batch):
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            p, o, m = jstep(state["params"], state["opt"], batch)
            return {"params": p, "opt": o}, m

        def on_metrics(s, m):
            if (s + 1) % args.log_every == 0:
                dt = time.time() - t0
                toks = (s + 1 - start) * gbs * seq
                log.info("step %d loss %.4f lr %.2e | %.0f tok/s", s + 1,
                         float(m["loss"]), float(m["lr"]), toks / max(dt, 1e-9))

        state, hb = ft.run_loop(fcfg, state, step_fn, pipe, start, args.steps,
                                on_metrics)
        log.info("done: %d steps, %d stragglers", args.steps,
                 len(hb.straggler_steps))


if __name__ == "__main__":
    main()
