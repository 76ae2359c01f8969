"""End-to-end LM training driver: data pipeline -> sharded train step ->
checkpoints -> fault-tolerance hooks.

Default runs a ~10M-param model for 60 steps on CPU in a couple of
minutes; ``--size 100m --steps 300`` is the full exercise.

    PYTHONPATH=src python examples/train_lm.py [--size 100m] [--steps 300]
        [--arch qwen2-7b] [--microbatches 2] [--compress int8]
"""
import argparse

import jax

from repro.configs import ARCHS
from repro.data.synthetic import ShardedTokenStream
from repro.models.registry import get_model
from repro.train.optimizer import AdamW, cosine_schedule
from repro.train.trainer import Trainer, TrainerConfig

SIZES = {
    # (layers, d_model, heads, kv, d_ff)  — ~param counts with 8k vocab
    "10m": (4, 256, 4, 2, 1024),
    "100m": (12, 768, 12, 4, 3072),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-7b", choices=sorted(ARCHS))
    ap.add_argument("--size", default="10m", choices=sorted(SIZES))
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--compress", default=None, choices=[None, "bf16", "int8"])
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    args = ap.parse_args()

    n_layers, d_model, heads, kv, d_ff = SIZES[args.size]
    cfg = ARCHS[args.arch].scaled(
        n_layers=n_layers, d_model=d_model, n_heads=heads, n_kv_heads=kv,
        d_head=d_model // heads, d_ff=d_ff, vocab_size=8192,
        param_dtype="float32", compute_dtype="float32", remat=False,
        q_chunk=128, kv_chunk=128)
    if cfg.family == "moe":
        cfg = cfg.scaled(n_experts=8, experts_per_token=2, moe_d_ff=d_ff // 2)
    api = get_model(cfg)
    n_params = sum(x.size for x in jax.tree.leaves(
        jax.eval_shape(api.init, jax.random.PRNGKey(0))))
    print(f"arch family {cfg.family}; params {n_params / 1e6:.1f}M")

    data = ShardedTokenStream(cfg.vocab_size, args.seq, args.batch, seed=0)
    opt = AdamW(lr=cosine_schedule(3e-4, warmup=20, total=args.steps))
    trainer = Trainer(
        api, opt, iter(data), ckpt_dir=args.ckpt_dir,
        tcfg=TrainerConfig(total_steps=args.steps, ckpt_every=50,
                           log_every=10, microbatches=args.microbatches,
                           grad_compression=args.compress))
    state = trainer.init_or_restore(jax.random.PRNGKey(0))
    state = trainer.run(state)
    losses = trainer.losses()
    print(f"loss: first10 {losses[:10].mean():.4f} -> "
          f"last10 {losses[-10:].mean():.4f}")
    assert losses[-10:].mean() < losses[:10].mean(), "loss did not improve"
    print("train_lm complete ✓")


if __name__ == "__main__":
    main()
