"""End-to-end training example: train a ~100M-parameter LLaMA-style dense
LM for a few hundred steps with the full production stack — data pipeline,
AOT-compiled train step (the paper's init/launch split at training scale),
async arena checkpoints, and restart-safe resume.

Run:  PYTHONPATH=src python examples/train_lm.py [--steps 300] [--tiny]
(``--tiny`` shrinks to seconds for CI; the default ~100M config is sized
for a real machine.)
"""
import argparse
import os
import tempfile

import jax

from repro.core import enable_compile_cache
from repro.data.pipeline import StreamConfig, TokenStream
from repro.models import build_model
from repro.models.common import ArchConfig
from repro.optim import AdamWConfig, Schedule
from repro.train import TrainConfig, Trainer, TrainerConfig


def lm_100m() -> ArchConfig:
    """~106M params: 12L, d=768, 12H (GQA kv=4), ff=2048, vocab=32k."""
    return ArchConfig(
        name="lm-100m", family="dense", n_layers=12, d_model=768, n_heads=12,
        n_kv_heads=4, d_head=64, d_ff=2048, vocab=32000,
        param_dtype="float32", dtype="float32")


def lm_tiny() -> ArchConfig:
    return ArchConfig(
        name="lm-tiny", family="dense", n_layers=2, d_model=128, n_heads=4,
        n_kv_heads=2, d_head=32, d_ff=256, vocab=512,
        param_dtype="float32", dtype="float32")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = lm_tiny() if args.tiny else lm_100m()
    model = build_model(cfg)
    n_params = sum(
        int(p.size) for p in jax.tree.leaves(
            jax.eval_shape(model.init_params, jax.random.key(0))))
    print(f"model: {cfg.name}, {n_params / 1e6:.1f}M params")

    stream = TokenStream(StreamConfig(vocab=cfg.vocab, seq=args.seq,
                                      batch=args.batch, seed=0))
    ckpt_dir = args.ckpt_dir or os.path.join(tempfile.gettempdir(), "repro_lm")
    tcfg = TrainerConfig(
        total_steps=args.steps, ckpt_dir=ckpt_dir, ckpt_interval=100,
        log_every=max(1, args.steps // 20),
        train=TrainConfig(opt=AdamWConfig(schedule=Schedule(
            base_lr=3e-4, warmup_steps=args.steps // 10 + 1,
            total_steps=args.steps))))
    trainer = Trainer(model, tcfg)
    trainer.fit(stream, jax.random.key(0))
    first, last = trainer.history[0][1], trainer.history[-1][1]
    print(f"loss {first:.4f} -> {last:.4f} over {args.steps} steps")
    assert last < first, "loss must improve"


if __name__ == "__main__":
    main()
