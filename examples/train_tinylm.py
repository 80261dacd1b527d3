"""End-to-end training driver: train a small LM for a few hundred steps on
CPU with checkpointing + restart; scale knobs reach ~100M params for real
hardware runs.

    PYTHONPATH=src python examples/train_tinylm.py --steps 300
    # ~100M-param config (for TPU-class hardware):
    PYTHONPATH=src python examples/train_tinylm.py --d-model 768 \
        --layers 12 --vocab 32000 --steps 300
"""
import argparse

from repro.launch.train import train
from repro.models.config import ArchConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--d-model", type=int, default=256)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--vocab", type=int, default=2048)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt-dir", default="/tmp/tinylm_ckpt")
    args = ap.parse_args()

    cfg = ArchConfig(
        name="tinylm", family="dense",
        n_layers=args.layers, d_model=args.d_model,
        n_heads=max(2, args.d_model // 64),
        n_kv_heads=max(2, args.d_model // 64),
        d_ff=args.d_model * 4, vocab=args.vocab,
        remat=False, dtype="float32")
    print(f"[tinylm] params ~ {cfg.param_count()/1e6:.1f}M")

    # the production training driver takes a custom config directly
    res = train(cfg, steps=args.steps, batch=args.batch, seq=args.seq,
                ckpt_dir=args.ckpt_dir, ckpt_every=50, reduced=False,
                base_lr=3e-3)
    print(f"[tinylm] loss {res['first_loss']:.3f} -> {res['final_loss']:.3f} "
          f"over {args.steps} steps")
    assert res['final_loss'] < res['first_loss'], "loss must decrease"


if __name__ == "__main__":
    main()
