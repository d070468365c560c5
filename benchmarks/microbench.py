"""Hot-path microbenchmarks: simulator throughput, telemetry, kernels
(interpret mode — correctness-path cost, not TPU perf)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from benchmarks.common import emit, timeit_us


def main() -> None:
    # simulator event throughput
    from repro.core import SimConfig, Simulation, StraightLinePolicy
    from repro.core.testbed import paper_tiers
    from repro.core.workload import ramp

    reqs = ramp(4000, seed=0)
    t0 = time.perf_counter()
    Simulation(StraightLinePolicy(), paper_tiers(seed=0), SimConfig()).run(reqs)
    dt = time.perf_counter() - t0
    emit("micro.simulator", dt / len(reqs) * 1e6, f"requests_per_s={len(reqs)/dt:.0f}")

    from repro.core.telemetry import FrequencyEstimator

    fe = FrequencyEstimator()
    box = [0.0]

    def obs():
        box[0] += 0.01
        fe.observe(box[0])

    emit("micro.telemetry.observe", timeit_us(obs, n=5000), "")

    # engine decode step (reduced model, real JAX execution)
    from repro.configs.registry import get_config
    from repro.serving.engine import EngineConfig, InferenceEngine

    cfg = get_config("smollm-360m", smoke=True).replace(attn_chunk=64)
    eng = InferenceEngine(cfg, EngineConfig(max_slots=4, max_len=128, max_new_tokens=8))
    for p in ([1, 2, 3], [4, 5], [6], [7, 8, 9]):
        eng.submit(list(p))
    eng.step()
    us = timeit_us(lambda: eng.step(), n=20)
    emit("micro.engine.decode_step", us, f"slots=4;toks_per_s={4/(us/1e6):.0f}")

    # optimizer update
    from repro.train.optimizer import OptConfig, adamw_update, init_opt_state

    params = {"w": jnp.zeros((1024, 256))}
    ocfg = OptConfig()
    opt = init_opt_state(params, ocfg)
    g = {"w": jnp.ones((1024, 256)) * 1e-3}
    upd = jax.jit(lambda g, o, p: adamw_update(g, o, p, ocfg))
    upd(g, opt, params)
    emit("micro.adamw.262k_params", timeit_us(lambda: jax.block_until_ready(upd(g, opt, params)), n=50), "")


if __name__ == "__main__":
    from repro.jax_cache import enable_compile_cache

    enable_compile_cache()
    main()
