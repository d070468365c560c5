"""The scheduler table: StraightLine (Algorithm 1) vs static / round-robin /
random / SLO-aware / adaptive-thresholds under the mixed bimodal ramp."""
from __future__ import annotations

from benchmarks.common import emit, timeit_us
from repro.core import (
    RandomPolicy,
    Request,
    RoundRobinPolicy,
    SimConfig,
    Simulation,
    SLOAwarePolicy,
    StaticPolicy,
    StraightLinePolicy,
    Thresholds,
    Tier,
)
from repro.core.estimator import LatencyEstimator, transfer_time
from repro.core.testbed import paper_tiers
from repro.core.workload import ramp

LOADS = [1000, 3000, 6000]


def slo_policy(tiers):
    models = {
        t: (lambda sim: (lambda r, f: LatencyEstimator.service_time(sim.app, r.work_units, sim.cfg.slice_)
             + transfer_time(r.data_size, sim.cfg.net_bw) + sim.cfg.activation_s))(sim)
        for t, sim in tiers.items()
    }
    return SLOAwarePolicy(models)


def main() -> None:
    for load in LOADS:
        tiers0 = paper_tiers(seed=1)
        policies = [
            StraightLinePolicy(),
            StaticPolicy(Tier.FLASK),
            StaticPolicy(Tier.DOCKER),
            StaticPolicy(Tier.SERVERLESS),
            RoundRobinPolicy(),
            RandomPolicy(),
            slo_policy(tiers0),
        ]
        for pol in policies:
            sim = Simulation(pol, paper_tiers(seed=1), SimConfig())
            s = sim.run(ramp(load, dist="bimodal", seed=load)).summary()
            emit(
                f"placement.{pol.name}.load{load}",
                s["median_response_s"] * 1e6,
                f"fail_rate={s['failure_rate']:.3f};p95_s={s['p95_response_s']:.2f}",
            )

    # decision-latency microbenches (router hot path)
    pol = StraightLinePolicy(Thresholds())
    r = Request(rid=0, arrival_t=0.0, data_size=2e5)
    us = timeit_us(lambda: pol.place(r, 900.0, 1, 1), n=5000)
    emit("placement.decide.python", us, "single-request Algorithm 1")

    import jax
    import jax.numpy as jnp

    from repro.core.placing import placing_batch_jax

    sizes = jnp.asarray([1e5] * 1024, jnp.float32)
    fn = jax.jit(lambda s: placing_batch_jax(900.0, s, 4, 8, F=1200.0, D=1e6))
    fn(sizes).block_until_ready()
    us = timeit_us(lambda: fn(sizes).block_until_ready(), n=200)
    emit("placement.decide.jax_batch1024", us, f"per_req_ns={us/1024*1000:.1f}")


if __name__ == "__main__":
    from repro.jax_cache import enable_compile_cache

    enable_compile_cache()
    main()
