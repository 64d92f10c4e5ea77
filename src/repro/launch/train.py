"""Training driver: end-to-end fault-tolerant train loop.

Runs anywhere: on CPU it uses a 1x1 mesh and a smoke config; on a pod
the same code takes the production mesh (``--production``).  Wraps the
jitted train step in :class:`repro.runtime.fault.FaultTolerantLoop`
(periodic async checkpoints, restart on failure, straggler watchdog).

  PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --steps 100 \
      --smoke --ckpt-dir /tmp/ckpt
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from .. import models
from ..configs import get_config, smoke_config
from ..checkpoint.ckpt import Checkpointer
from ..data.pipeline import DataConfig, TokenStream
from ..optim import adamw
from ..parallel import compress
from ..runtime.fault import FailureInjector, FaultTolerantLoop
from .compile_cache import init_compile_cache


def build_step(cfg, opt_cfg, use_compression: bool = False):
    def train_step(state, batch):
        params, opt_state, ef = state
        loss, grads = jax.value_and_grad(
            lambda p: models.loss_fn(cfg, p, batch))(params)
        if use_compression:
            grads, ef = compress.compressed_grads(grads, ef)
        params, opt_state, metrics = adamw.apply(
            opt_cfg, opt_state, grads, params)
        metrics["loss"] = loss
        return (params, opt_state, ef), metrics

    return jax.jit(train_step, donate_argnums=(0,))


def main(argv=None):
    init_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--save-every", type=int, default=20)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--inject-failure-at", type=int, default=None)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    opt_cfg = adamw.AdamWConfig(warmup_steps=10, decay_steps=args.steps)

    params = models.init_params(cfg, jax.random.PRNGKey(0))
    opt_state = adamw.init(params)
    ef = compress.init_error_feedback(params) if args.compress_grads else None

    stream = TokenStream(DataConfig(
        vocab=cfg.vocab, seq_len=args.seq, global_batch=args.batch))
    step_fn = build_step(cfg, opt_cfg, args.compress_grads)
    losses = []

    def wrapped_step(state, batch):
        new_state, metrics = step_fn(state, {
            "tokens": jnp.asarray(batch["tokens"]),
            "labels": jnp.asarray(batch["labels"])})
        losses.append(float(metrics["loss"]))
        return new_state

    injector = None
    if args.inject_failure_at is not None:
        injector = FailureInjector(
            fail_at={args.inject_failure_at: RuntimeError("injected")})

    loop = FaultTolerantLoop(
        wrapped_step, stream.batch_at,
        Checkpointer(args.ckpt_dir), save_every=args.save_every,
        injector=injector)
    t0 = time.time()
    state = loop.run((params, opt_state, ef), args.steps)
    dt = time.time() - t0
    print(f"trained {loop.stats.completed_steps} steps in {dt:.1f}s "
          f"({loop.stats.restarts} restarts, "
          f"{loop.stats.straggler_steps} straggler steps)")
    print(f"loss: {losses[0]:.4f} -> {losses[-1]:.4f}")
    return state, losses


if __name__ == "__main__":
    main()
