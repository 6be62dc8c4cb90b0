"""Training launcher: any assigned architecture on any mesh.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b \
        --smoke --steps 20 [--data 1 --model 1] [--grad-accum 2] \
        [--compress-grads] [--ckpt-dir /tmp/ckpt]

On this CPU container use --smoke (reduced config). On a real pod, drop
--smoke and size --data/--model to the slice (the same code path the
512-device dry-run exercises). Fault tolerance comes from the elastic
driver: failures detected between steps trigger re-mesh + restore.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.configs import ARCH_IDS, get_config
from repro.data import LMTaskConfig, lm_batches
from repro.distributed import compression, sharding as sh
from repro.launch.compile_cache import enable_compile_cache
from repro.models import get_model
from repro.runtime import ElasticTrainer
from repro.train import get_optimizer, make_train_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="qwen2-0.5b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--model", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--compress-grads", action="store_true",
                    help="INT8 error-feedback gradient compression")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--save-every", type=int, default=10)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    api = get_model(cfg)
    opt = get_optimizer(cfg.optimizer, lr=args.lr)

    err_state = {}

    def make_state(mesh):
        params = api.init(jax.random.PRNGKey(0))
        aparams = jax.eval_shape(lambda: params)
        pspec = sh.param_shardings(aparams, mesh, cfg)
        params = jax.device_put(params, pspec)
        astate = jax.eval_shape(opt.init, aparams)
        ospec = sh.opt_state_shardings(astate, aparams, mesh, cfg)
        opt_state = jax.jit(opt.init, out_shardings=ospec)(params)

        grad_transform = None
        if args.compress_grads:
            err_state["e"] = compression.init_error_state(params)

            def grad_transform(grads):  # noqa: F811
                g, err_state["e"] = compression.apply_error_feedback(
                    grads, err_state["e"])
                return g

        raw = make_train_step(api.loss_fn, opt, grad_accum=args.grad_accum,
                              grad_transform=grad_transform)
        jitted = jax.jit(raw)

        def step_fn(p, o, b, mesh):
            with jax.set_mesh(mesh):
                return jitted(p, o, b)

        return params, opt_state, step_fn, (pspec, ospec)

    gen = lm_batches(LMTaskConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                                  batch_size=args.batch))

    def batches():
        for b in gen:
            batch = {k: jnp.asarray(v) for k, v in b.items()}
            if cfg.family == "encdec":
                batch["frames"] = jnp.zeros(
                    (args.batch, args.seq, cfg.d_model), jnp.float32)
            if cfg.family == "vlm":
                batch["prefix_embeds"] = jnp.zeros(
                    (args.batch, cfg.num_prefix_embeds, cfg.d_model),
                    jnp.float32)
            yield batch

    trainer = ElasticTrainer(make_state=make_state,
                             ckpt=CheckpointManager(args.ckpt_dir, keep=3),
                             save_every=args.save_every,
                             model_parallel=args.model)
    t0 = time.time()
    out = trainer.run(batches(), num_steps=args.steps)
    dt = time.time() - t0
    print(f"{args.arch}: {args.steps} steps in {dt:.1f}s; "
          f"loss {out['losses'][0]:.3f} -> {out['losses'][-1]:.3f}; "
          f"restarts {out['restarts']}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
