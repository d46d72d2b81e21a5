"""End-to-end driver: train a ~100M-param LM with top-k selective
attention for a few hundred steps on synthetic data, with checkpointing
and restart support.

Config: 12L, d_model=768, 12 heads, d_ff=3072, vocab 32k → ~124M params
(GPT-2-small-class).  Top-k attention (k=32) is the SATA workload; the
same model runs dense attention with --dense for an accuracy A/B.

Run:  PYTHONPATH=src python examples/train_lm.py --steps 300
(CPU-sized: ~1-2 s/step at batch 8 × seq 128.)
"""
import argparse
import os
import tempfile

from repro.launch.compile_cache import use_compile_cache
from repro.launch.train import train
from repro.models.config import ModelConfig
import repro.launch.train as T
import repro.configs.archs as A


def lm100m(dense: bool = False) -> ModelConfig:
    return ModelConfig(
        name="lm100m", family="dense", n_layers=12, d_model=768,
        n_heads=12, n_kv_heads=12, d_ff=3072, vocab_size=32000,
        head_dim=64, attention_variant="dense" if dense else "topk",
        topk_k=32, q_chunk=128, dtype="float32", remat="none")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--dense", action="store_true")
    ap.add_argument("--ckpt-dir",
                    default=os.path.join(tempfile.gettempdir(), "sata_lm100m"))
    args = ap.parse_args()
    use_compile_cache()

    cfg = lm100m(args.dense)
    print(f"[train_lm] {cfg.name}: {cfg.param_count()/1e6:.0f}M params, "
          f"attention={cfg.attention_variant}")
    # register so the generic launcher can use it (mutate in place — the
    # launcher holds a direct reference to this dict)
    A.SMOKE["lm100m"] = cfg
    out = train("lm100m", smoke=True, steps=args.steps, batch=args.batch,
                seq=args.seq, ckpt_dir=args.ckpt_dir, ckpt_every=50,
                log_every=10)
    ls = out["losses"]
    print(f"[train_lm] loss {ls[0]:.3f} → {ls[-1]:.3f} over {len(ls)} steps "
          f"({out['stragglers']} straggler steps flagged)")
    if args.steps >= 50:          # short runs sit inside LR warmup
        assert min(ls[-10:]) < ls[0], "loss did not decrease"


if __name__ == "__main__":
    main()
