"""End-to-end training driver: train a ~100M-parameter model for a few
hundred steps on CPU and verify the loss decreases.

    PYTHONPATH=src python examples/train_100m.py [--steps 300]

Uses the tinyllama family scaled to ~100M params on a synthetic Markov
token stream (repro/data/pipeline.py), with the in-house AdamW + cosine
schedule and checkpointing.
"""

import argparse
import sys

sys.path.insert(0, "src")

import dataclasses

from repro import configs
from repro.data.pipeline import SyntheticTokens
from repro.models import flops
from repro.models.transformer import Model
from repro.train import trainer


def build_100m_config():
    base = configs.get("tinyllama-1.1b", reduced=True)
    cfg = dataclasses.replace(
        base, name="tinyllama-100m",
        n_layers=8, d_model=768, n_heads=12, n_kv_heads=4, d_ff=2048,
        vocab=8192, head_dim=None,
    )
    cfg.validate()
    return cfg


def main():
    ap = argparse.ArgumentParser()
    # default sized for a 1-core CPU container; on real hardware run
    # --steps 300+ (the loss keeps falling)
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    args = ap.parse_args()

    cfg = build_100m_config()
    total, _ = flops.param_count(cfg)
    print(f"config {cfg.name}: {total / 1e6:.0f}M params")
    model = Model(cfg)
    data = iter(SyntheticTokens(vocab=cfg.vocab, seq_len=args.seq,
                                batch=args.batch, seed=0))
    state, history = trainer.train_loop(
        model, data, steps=args.steps,
        peak_lr=1e-3, warmup=min(20, max(args.steps // 3, 1)), total=args.steps,
    )
    first, last = history[0]["loss"], history[-1]["loss"]
    print(f"\nloss {first:.4f} -> {last:.4f} "
          f"({(1 - last / first) * 100:.1f}% reduction over {args.steps} steps)")
    # short CPU runs spend most steps inside warmup; only gate longer runs
    want = 0.98 if args.steps >= 60 else 0.995
    assert last < first * want, "training failed to reduce loss"
    print("OK: the model learns the planted Markov structure.")


if __name__ == "__main__":
    from repro.runtime import use_compile_cache

    use_compile_cache()
    main()
