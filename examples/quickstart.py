"""Quickstart: the public API in ~60 lines.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp

from repro.configs.registry import get_config, list_archs
from repro.core import Request, SimConfig, Simulation, StraightLinePolicy, Thresholds, Tier
from repro.core.testbed import paper_tiers
from repro.core.workload import ramp
from repro.jax_cache import enable_compile_cache
from repro.models import get_model

enable_compile_cache()

print("assigned architectures:", ", ".join(list_archs()))

# --- 1. any architecture, one API -----------------------------------------
cfg = get_config("glm4-9b", smoke=True)          # structurally-faithful reduction
model = get_model(cfg)
params = model.init(jax.random.PRNGKey(0))
batch = {
    "tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size),
    "labels": jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0, cfg.vocab_size),
}
loss, metrics = model.loss(None, params, batch)
print(f"glm4-9b (smoke) train loss: {float(loss):.3f}")

# --- 2. prefill + decode ----------------------------------------------------
tok, cache = model.prefill(None, params, {"tokens": batch["tokens"]}, cap=24)
tok2, cache = model.decode(None, params, cache, {"token": tok[:, None], "cache_index": jnp.asarray(16)})
print("greedy next tokens:", tok.tolist(), "->", tok2.tolist())

# --- 3. StraightLine: Algorithm 1 ------------------------------------------
pol = StraightLinePolicy(Thresholds(F=1200, D=1e6))
d = pol.place(Request(rid=0, arrival_t=0.0, data_size=2e5), f_t=2000, flask_free=1, docker_free=1)
print(f"burst+small payload -> {d.tier.name}  ({d.reason})")

# --- 4. the hybrid testbed under a paper-style ramp --------------------------
sim = Simulation(pol, paper_tiers(seed=0), SimConfig())
summary = sim.run(ramp(2000, seed=0)).summary()
print("2000-session ramp through StraightLine:", summary)
