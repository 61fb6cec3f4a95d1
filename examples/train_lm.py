"""End-to-end LM training example: train a ~small dense model for a few
hundred steps on local devices with checkpointing, then show restart.

Defaults are CPU-sized; on a real slice pass --arch/--steps and a mesh
via repro.lm.launch.train instead.

  PYTHONPATH=src python examples/train_lm.py --steps 120
"""
import argparse
import dataclasses

from repro.lm.configs import get_config
from repro.data.pipeline import TokenPipeline
from repro.lm.models.model import Model
from repro.lm.train.optimizer import AdamW, cosine_schedule
from repro.lm.train.trainer import Trainer, TrainerConfig
from repro.compile_cache import enable_compile_cache

enable_compile_cache()

ap = argparse.ArgumentParser()
ap.add_argument("--steps", type=int, default=120)
ap.add_argument("--ckpt-dir", default="/tmp/repro_example_lm")
args = ap.parse_args()

cfg = dataclasses.replace(
    get_config("phi3-medium-14b").reduced(),
    n_layers=4, d_model=128, d_ff=256, vocab=512)
model = Model(cfg)
pipe = TokenPipeline(vocab=cfg.vocab, seq_len=64, global_batch=16, seed=0)
opt = AdamW(lr=cosine_schedule(1e-3, warmup=20, total=args.steps),
            weight_decay=0.01)
tcfg = TrainerConfig(steps=args.steps, ckpt_every=50, ckpt_dir=args.ckpt_dir,
                     log_every=20, async_ckpt=True)
trainer = Trainer(model, opt, pipe, tcfg)
state = trainer.run()
print("history:")
for row in trainer.history:
    print(f"  step {row['step']:4d}  ce={row['ce']:.4f}  "
          f"gnorm={row['grad_norm']:.3f}")
assert trainer.history[-1]["ce"] < trainer.history[0]["ce"]
print(f"checkpoints at: {trainer.ckpt.all_steps()} (resumable — rerun me)")
