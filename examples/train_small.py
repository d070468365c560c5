"""Train a reduced model end-to-end with checkpoint/restart fault tolerance.

    PYTHONPATH=src python examples/train_small.py [--steps 120] [--arch smollm-360m]
"""
import argparse
import shutil
import tempfile

from repro.configs.registry import get_config
from repro.jax_cache import enable_compile_cache
from repro.models import get_model
from repro.train import checkpoint as ckpt
from repro.train.data import DataConfig
from repro.train.optimizer import OptConfig
from repro.train.schedule import WarmupCosine
from repro.train.trainer import TrainConfig, Trainer

ap = argparse.ArgumentParser()
ap.add_argument("--steps", type=int, default=120)
ap.add_argument("--arch", default="smollm-360m")
ap.add_argument("--ckpt-dir", default=None)
args = ap.parse_args()
enable_compile_cache()

ckpt_dir = args.ckpt_dir or tempfile.mkdtemp(prefix="straightline_ckpt_")
cfg = get_config(args.arch, smoke=True).replace(attn_chunk=16, ce_chunks=2)
model = get_model(cfg)


def make_trainer(steps):
    return Trainer(
        model, None,
        TrainConfig(steps=steps, ckpt_every=20, ckpt_dir=ckpt_dir, log_every=10,
                    opt=OptConfig(lr=2e-3)),
        DataConfig(batch_size=4, seq_len=64, vocab_size=cfg.vocab_size, seed=7),
        schedule=WarmupCosine(peak_lr=2e-3, warmup_steps=10, total_steps=args.steps),
    )


half = args.steps // 2
print(f"training {args.arch} (smoke) for {half} steps, then simulating a crash...")
r1 = make_trainer(half).run(seed=0)
print(f"  crashed at step {r1['steps_done']}; latest checkpoint: {ckpt.latest_step(ckpt_dir)}")

print("restarting — auto-resume from checkpoint:")
r2 = make_trainer(args.steps).run(seed=0)
hist = r2["history"]
print(f"  resumed and finished at step {r2['steps_done']}")
print(f"  loss: {hist[0]['loss']:.3f} (start) -> {hist[-1]['loss']:.3f} (final)")
assert hist[-1]["loss"] < hist[0]["loss"]
shutil.rmtree(ckpt_dir, ignore_errors=True)
print("OK — checkpoint/restart training complete")
