"""End-to-end training driver: data pipeline -> FT runtime -> checkpoints ->
telemetry, runnable on CPU with a reduced config or on a real mesh with the
full config.

    python -m repro.launch.train --arch qwen3-4b --reduced --steps 200
    python -m repro.launch.train --preset quickstart-100m --steps 300
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from pathlib import Path

import jax
import numpy as np

from repro.configs import CONFIGS, TrainConfig, get_config
from repro.configs.base import ModelConfig
from repro.core import (CheckpointManager, FTTrainLoop, MetricsRegistry,
                        job_mtbf_seconds)
from repro.data import (DeterministicLoader, LoaderConfig, TokenDataset,
                        synthetic_corpus, write_token_shards)
from repro.launch.compile_cache import enable_compile_cache
from repro.models import LM, ForwardOpts
from repro.train import init_train_state, make_train_step

# run artifacts stay inside the checkout, in its git-ignored experiments/
RUN_DIR = Path(__file__).resolve().parents[3] / "experiments"

QUICKSTART_100M = ModelConfig(
    name="quickstart-100m", family="dense", num_layers=12, d_model=768,
    num_heads=12, num_kv_heads=4, head_dim=64, d_ff=3072, vocab_size=32000)


def build_config(args) -> ModelConfig:
    if args.preset == "quickstart-100m":
        return QUICKSTART_100M
    cfg = get_config(args.arch)
    return cfg.reduced() if args.reduced else cfg


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b",
                    choices=sorted(CONFIGS) + ["quickstart-100m"])
    ap.add_argument("--preset", default=None)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--data-dir", default=str(RUN_DIR / "data"))
    ap.add_argument("--ckpt-dir", default=str(RUN_DIR / "ckpt"))
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="0 = Young's formula from measured step time")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = build_config(args)
    print(f"arch={cfg.name} params={cfg.param_count()/1e6:.1f}M "
          f"family={cfg.family}")

    # --- data ---------------------------------------------------------------
    data_dir = Path(args.data_dir) / cfg.name
    if not (data_dir / "index.txt").exists():
        toks = synthetic_corpus(max(2_000_000, args.batch * args.seq * 20),
                                cfg.vocab_size, seed=0)
        write_token_shards(str(data_dir), toks)
    ds = TokenDataset(str(data_dir))
    loader = DeterministicLoader(ds, LoaderConfig(args.batch, args.seq))

    # --- model / trainer -----------------------------------------------------
    lm = LM(cfg)
    tcfg = TrainConfig(learning_rate=args.lr, warmup_steps=20,
                       total_steps=args.steps)
    opts = ForwardOpts(attn_impl="blockwise", q_chunk=min(args.seq, 512),
                       kv_chunk=min(args.seq, 512), remat="none")
    state = init_train_state(lm, jax.random.key(0), tcfg)
    step = jax.jit(make_train_step(lm, tcfg, opts,
                                   microbatches=args.microbatches))

    # --- warmup to measure step time for Young's interval --------------------
    b0 = loader.batch_at(0)
    t0 = time.perf_counter()
    state, _ = step(state, b0)
    jax.block_until_ready(state["step"])
    t_compile = time.perf_counter() - t0
    t0 = time.perf_counter()
    state, _ = step(state, b0)
    jax.block_until_ready(state["step"])
    step_time = time.perf_counter() - t0
    ckpt_every = args.ckpt_every or CheckpointManager(
        args.ckpt_dir, delta_seconds=max(step_time, 1.0),
        mtbf_seconds=job_mtbf_seconds(96), step_time=step_time).every
    ckpt_every = min(ckpt_every, max(args.steps // 3, 1))
    print(f"compile={t_compile:.1f}s step={step_time*1e3:.0f}ms "
          f"ckpt_every={ckpt_every}")

    # --- FT loop --------------------------------------------------------------
    reg = MetricsRegistry()
    loop = FTTrainLoop(step, state, args.ckpt_dir, ckpt_every, registry=reg)
    t0 = time.perf_counter()
    final = loop.run(loader.batch_at, args.steps)
    wall = time.perf_counter() - t0
    for m in loop.metrics_log:
        if m["step"] % args.log_every == 0 or m["step"] == args.steps - 1:
            print(f"step {m['step']:5d} loss {m['loss']:.4f} "
                  f"gnorm {m['grad_norm']:.3f} lr {m['lr']:.2e}")
    toks = args.steps * args.batch * args.seq
    print(f"done: {args.steps} steps, {toks/wall:.0f} tok/s, "
          f"{reg.counter('checkpoints_written').get():.0f} checkpoints, "
          f"final loss {loop.metrics_log[-1]['loss']:.4f}")


if __name__ == "__main__":
    main()
