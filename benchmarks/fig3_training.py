"""Paper Fig 3: model-development convergence on two resource profiles.

The paper trains Xception on a CPU vs GPU cluster and reports that the GPU
cluster reaches stable accuracy in 1-2 epochs vs 9-10. The analogue here:
the same reduced LM trained under a small-batch profile (CPU-class) and a
large-batch profile (accelerator-class); the large-batch profile reaches the
loss target in fewer optimizer steps. Also trains the Xception-analog
classifier itself (the paper's own app model)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit
from repro.configs.registry import get_config
from repro.models import get_model
from repro.models.xception import XceptionConfig, init, loss_fn
from repro.train.data import DataConfig
from repro.train.optimizer import OptConfig
from repro.train.trainer import TrainConfig, Trainer


def lm_profile(name: str, batch: int, steps: int) -> None:
    cfg = get_config("smollm-360m", smoke=True).replace(attn_chunk=16, ce_chunks=2)
    model = get_model(cfg)
    tr = Trainer(
        model, None,
        TrainConfig(steps=steps, ckpt_every=10**9, ckpt_dir=None, log_every=1, opt=OptConfig(lr=2e-3)),
        DataConfig(batch_size=batch, seq_len=32, vocab_size=cfg.vocab_size, seed=5),
    )
    r = tr.run(seed=0)
    losses = [h["loss"] for h in r["history"]]
    target = 4.5
    hit = next((h["step"] for h in r["history"] if h["loss"] < target), -1)
    emit(
        f"fig3.lm.{name}",
        r["wall_s"] / max(1, r["steps_done"]) * 1e6,
        f"loss0={losses[0]:.3f};lossN={losses[-1]:.3f};steps_to_{target}={hit}",
    )


def xception_train() -> None:
    cfg = XceptionConfig(img_size=32, width=16, n_blocks=2)
    params = init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    # 4-class synthetic image task (class-dependent color bias => learnable)
    def batch(step):
        lab = rng.integers(0, 4, 32)
        img = rng.normal(0, 1, (32, cfg.img_size, cfg.img_size, 3)).astype(np.float32)
        img[..., 0] += lab[:, None, None] * 1.5
        return jnp.asarray(img), jnp.asarray(lab)

    opt_lr = 1e-2

    @jax.jit
    def step(params, img, lab):
        (l, m), g = jax.value_and_grad(lambda p: loss_fn(cfg, p, img, lab), has_aux=True)(params)
        params = jax.tree.map(lambda p, gg: p - opt_lr * gg, params, g)
        return params, m

    accs = []
    for i in range(60):
        img, lab = batch(i)
        params, m = step(params, img, lab)
        accs.append(float(m["acc"]))
    emit(
        "fig3.xception_analog",
        0.0,
        f"acc_first10={np.mean(accs[:10]):.3f};acc_last10={np.mean(accs[-10:]):.3f}",
    )


def main() -> None:
    lm_profile("small_batch_cpu_profile", batch=2, steps=40)
    lm_profile("large_batch_accel_profile", batch=8, steps=40)
    xception_train()


if __name__ == "__main__":
    from repro.jax_cache import enable_compile_cache

    enable_compile_cache()
    main()
