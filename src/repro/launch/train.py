"""End-to-end training driver (example application + launch entrypoint).

Runs a real training loop on the available devices (CPU smoke ⇒ reduced
configs; TPU pod ⇒ full configs with the production mesh): data pipeline →
pjit'd train step (remat + sharding rules) → checkpoint cadence → restart on
failure via the FT supervisor.

    PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --reduced \
        --steps 50 --batch 8 --seq 128 [--lora] [--resume]
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get_arch, reduced
from ..data.pipeline import DataConfig, SyntheticLMStream
from ..ft.supervisor import Supervisor
from ..models import build_model
from ..models.lora import lora_init, make_lora_loss
from ..train.optim import AdamW
from ..train.step import init_train_state, make_train_step
from ..ckpt.store import latest_step, restore_checkpoint
from .compile_cache import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--lora", action="store_true")
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--save-every", type=int, default=10)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--remat", default=None)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    model = build_model(cfg, remat=args.remat)
    key = jax.random.PRNGKey(0)
    opt = AdamW(lr=args.lr)

    stream = SyntheticLMStream(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq,
        global_batch=args.batch))

    if args.lora:
        base = model.init(key)
        adapters = lora_init(jax.random.PRNGKey(1), base)
        loss_fn = make_lora_loss(model, base)
        state = {"params": adapters, "opt": opt.init(adapters),
                 "step": jnp.zeros((), jnp.int32)}
        step_fn = jax.jit(make_train_step(model, opt,
                                          grad_accum=args.grad_accum,
                                          loss_fn=loss_fn))
    else:
        state = init_train_state(model, key, opt)
        step_fn = jax.jit(make_train_step(model, opt,
                                          grad_accum=args.grad_accum))

    start = 0
    if args.resume and latest_step(args.ckpt_dir) is not None:
        state, start = restore_checkpoint(args.ckpt_dir, state)
        print(f"resumed from step {start}")

    def batch_fn(step: int) -> dict:
        b = stream.batch(step)
        extra = {}
        if cfg.family == "encdec":
            extra["encoder_embeds"] = np.zeros(
                (args.batch, args.seq, cfg.d_model), np.float32)
        if cfg.frontend == "vit":
            extra["vision_embeds"] = np.zeros(
                (args.batch, cfg.n_frontend_tokens, cfg.d_model), np.float32)
        return {**b, **extra}

    sup = Supervisor(ckpt_dir=args.ckpt_dir, save_every=args.save_every)

    t0 = time.time()
    losses = []

    def timed_step(state, batch):
        state, metrics = step_fn(state, batch)
        losses.append(float(metrics["loss"]))
        print(f"step {int(state['step'])}: loss {float(metrics['loss']):.4f} "
              f"gnorm {float(metrics['grad_norm']):.3f}", flush=True)
        return state, metrics

    state, report = sup.run(state, timed_step, batch_fn, args.steps,
                            start_step=start)
    dt = time.time() - t0
    print(f"done: {report.steps_run} steps in {dt:.1f}s "
          f"({report.restarts} restarts); loss {losses[0]:.3f} → "
          f"{losses[-1]:.3f}")


if __name__ == "__main__":
    main()
