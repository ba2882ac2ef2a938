"""Serving driver: batched generation with CPM-powered KV management,
prompt-lookup speculative decoding and comparable-memory sampling.

CPU container: ``python -m repro.launch.serve --arch granite-8b --smoke``.
"""

import argparse
import logging
import time

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.distributed import sharding as shlib
from repro.launch.mesh import make_host_mesh
from repro.models import lm
from repro.serve import Engine, GenConfig

log = logging.getLogger("repro.launch.serve")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=0.0)
    ap.add_argument("--spec", type=int, default=0,
                    help="prompt-lookup draft length (batched; greedy only)")
    args = ap.parse_args()
    logging.basicConfig(level=logging.INFO)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    if args.spec and args.temperature > 0:
        log.warning("--spec is greedy-only; temperature>0 disables "
                    "speculation and falls back to the scan decode path")
    mesh = make_host_mesh()
    shlib.set_sharding_ctx(shlib.make_ctx(mesh))

    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    # speculative rounds may overshoot into cache slack; reserve draft room
    slack = 8 + 4 * args.spec
    engine = Engine(cfg, params, max_len=args.prompt_len + args.max_new + slack)
    tokens = jax.random.randint(jax.random.PRNGKey(1),
                                (args.batch, args.prompt_len), 0, cfg.vocab_size)
    gen = GenConfig(max_new_tokens=args.max_new, temperature=args.temperature,
                    top_k=args.top_k, top_p=args.top_p, ngram_spec=args.spec)

    t0 = time.time()
    out, stats = engine.generate({"tokens": tokens}, gen)
    jax.block_until_ready(out)
    dt = time.time() - t0
    new = args.batch * args.max_new
    log.info("generated %d tokens in %.2fs (%.1f tok/s)", new, dt, new / dt)
    if stats["proposed"]:
        log.info("spec decode: %d rounds, %d/%d draft tokens accepted "
                 "(rate %.2f)", stats["rounds"], stats["accepted"],
                 stats["proposed"], stats["acceptance_rate"])
    print(jnp.asarray(out)[:, -args.max_new:])


if __name__ == "__main__":
    main()
