"""Start the served path on one TPU chip and check what comes out.

    python3 chip_smoke.py

Refuses to run (exit 1, before any compile) unless JAX's first device is a
TPU; it never falls back to the CPU. Then, in this one process:

1. Kernel parity. Each Pallas kernel of the serving path runs compiled
   (``interpret=False``) at SmolLM-360M widths and is compared on the same
   chip with its jnp oracle: paged decode over a bf16 pool, an int8 pool
   and chained block tables; the bf16 and int8 prefill writes; flash
   attention; rmsnorm at an odd row count.
2. The served path. ``repro.launch.serve.serve()`` runs SmolLM-360M at its
   published width (32 layers, d_model 960, 15/5 heads, vocab 49152, bf16,
   seeded random weights) with the kernels on, prewarmed tiers and router
   workers: 16 requests of 16 to 512 prompt tokens and 32 new tokens each,
   through ``StraightLineRouter`` -> ``EngineLoop`` ->
   ``PagedInferenceEngine``. Every request must complete at full length
   with in-vocabulary ids, and every served engine's lowered decode and
   prefill programs must hold the Mosaic kernel (``tpu_custom_call``).
   The same prompts are served again on the jnp path; the share of greedy
   tokens that agree is printed as information only (random weights give
   near-flat logits, so near-ties may break either way).

Prints the compile seconds of each phase, the device memory in use after
it and the process's peak. The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
ARCH = "smollm-360m"
SEED = 0
REQUESTS, NEW_TOKENS = 16, 32
PS, SEQ, SLOTS = 16, 1024, 8     # the batch tier's page size, row, slots
ATOL = RTOL = 1e-2               # bf16 keeps 8 significant bits (2**-8 ~ 0.004)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class SmokeFailed(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailed(what)


def kernel_parity(cfg) -> None:
    """Phase 1: every serving kernel, compiled, against its oracle."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.flash_attention.kernel import flash_attention_bhsd
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.kernels.paged_attention.kernel import (
        paged_attention_grouped,
        paged_prefill_write_grouped,
        paged_prefill_write_grouped_quant,
    )
    from repro.kernels.paged_attention.ref import (
        paged_attention_ref,
        paged_prefill_write_quant_ref,
        paged_prefill_write_ref,
    )
    from repro.kernels.rmsnorm import ops as rms_ops
    from repro.models.common import rmsnorm as rmsnorm_ref
    from repro.models.quant import quantize_kv

    KV, H, HD, D = cfg.n_kv_heads, cfg.n_heads, cfg.hd, cfg.d_model
    G, P = H // KV, SEQ // PS
    NP = 1 + SLOTS * P
    rng = np.random.default_rng(SEED)
    bf16 = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)

    def report(name, out, ref, atol=ATOL, rtol=RTOL):
        out = np.asarray(jnp.asarray(out, jnp.float32))
        ref = np.asarray(jnp.asarray(ref, jnp.float32))
        check(out.shape == ref.shape, f"{name}: shape {out.shape} != {ref.shape}")
        check(bool(np.isfinite(out).all()), f"{name}: non-finite output")
        err = float(np.max(np.abs(out - ref)))
        ok = bool(np.all(np.abs(out - ref) <= atol + rtol * np.abs(ref)))
        print(f"  {name:<28} max abs err {err:.3e}  (tol {atol} + {rtol}*|ref|)  "
              f"{'ok' if ok else 'FAIL'}", flush=True)
        check(ok, f"{name}: max abs err {err} past tolerance")

    q = bf16(SLOTS, KV, G, HD)
    pool_k, pool_v = bf16(NP, KV, PS, HD), bf16(NP, KV, PS, HD)
    tab = jnp.asarray(rng.permutation(np.arange(1, NP))[: SLOTS * P].reshape(SLOTS, P),
                      jnp.int32)
    lens = rng.integers(1, SEQ + 1, SLOTS)
    lens[:2] = (SEQ, 1)                       # one full row, one single token
    lens = jnp.asarray(lens, jnp.int32)
    hi = jax.default_matmul_precision("highest")

    out = paged_attention_grouped(q, pool_k, pool_v, tab, lens, interpret=False)
    with hi:
        ref = paged_attention_ref(q, pool_k, pool_v, tab, lens)
    report("paged decode, bf16 pool", out, ref)

    kq, ks = quantize_kv(pool_k)
    vq, vs = quantize_kv(pool_v)
    out = paged_attention_grouped(q, kq, vq, tab, lens, interpret=False, pool_ks=ks, pool_vs=vs)
    with hi:
        ref = paged_attention_ref(q, kq, vq, tab, lens, pool_ks=ks, pool_vs=vs)
    report("paged decode, int8 pool", out, ref)

    tpp = PS                                   # table pages of 16 entries
    W1 = P // tpp
    l2 = jnp.concatenate([jnp.zeros((1, tpp), jnp.int32), tab.reshape(SLOTS * W1, tpp)])
    l1 = jnp.arange(1, 1 + SLOTS * W1, dtype=jnp.int32).reshape(SLOTS, W1)
    out = paged_attention_grouped(q, pool_k, pool_v, l1, lens, interpret=False, l2_tab=l2)
    with hi:
        ref = paged_attention_ref(q, pool_k, pool_v, l1, lens, l2_tab=l2)
    report("paged decode, chained tables", out, ref)

    Lp = 256                                   # a prompt of 16 pages into a 64-page row
    k, v = bf16(1, Lp, KV, HD), bf16(1, Lp, KV, HD)
    row = jnp.asarray(np.concatenate([rng.permutation(np.arange(1, NP))[: Lp // PS],
                                      np.zeros(P - Lp // PS)]), jnp.int32)
    zeros = jnp.zeros((NP, KV, PS, HD), jnp.bfloat16)
    outs = paged_prefill_write_grouped(zeros, zeros, k, v, row, interpret=False)
    refs = paged_prefill_write_ref(zeros, zeros, k, v, row)
    for name, o, r in zip(("K", "V"), outs, refs):
        report(f"prefill write bf16, {name} pool", o, r, atol=0.0, rtol=0.0)

    z8 = jnp.zeros((NP, KV, PS, HD), jnp.int8)
    zs = jnp.zeros((NP, KV, PS, 1), jnp.bfloat16)
    outs = paged_prefill_write_grouped_quant(z8, z8, zs, zs, k, v, row, interpret=False)
    refs = paged_prefill_write_quant_ref(z8, z8, zs, zs, k, v, row)
    for name, o, r in zip(("K codes", "V codes"), outs[:2], refs[:2]):
        report(f"prefill write int8, {name}", o, r, atol=1.0, rtol=0.0)   # one rounding step
    for name, o, r in zip(("K scales", "V scales"), outs[2:], refs[2:]):
        report(f"prefill write int8, {name}", o, r, atol=0.0, rtol=RTOL)

    S = 256
    qf, kf, vf = bf16(1, H, S, HD), bf16(1, KV, S, HD), bf16(1, KV, S, HD)
    out = flash_attention_bhsd(qf, kf, vf, interpret=False)
    with hi:
        ref = attention_ref(qf, kf, vf)
    report(f"flash attention, S={S}", out, ref)

    check(not rms_ops.interpret(), "rmsnorm ops would interpret on this backend")
    x, w = bf16(3, 1, D), bf16(D)
    report("rmsnorm, 3 rows", rms_ops.rmsnorm(x, w), rmsnorm_ref(x, w))


def served_path(use_pallas: bool):
    """Phase 2: one launcher run at the published width."""
    from repro.launch.serve import build_parser, serve

    argv = ["--arch", ARCH, "--requests", str(REQUESTS), "--max-new-tokens", str(NEW_TOKENS),
            "--prewarm", "--workers", "4"] + (["--use-pallas"] if use_pallas else [])
    return serve(build_parser().parse_args(argv))


def main() -> int:
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's first device is {dev.platform} "
              f"({dev.device_kind}). Not falling back to it.", file=sys.stderr)
        return 1
    print(f"device: {dev.device_kind}, platform {dev.platform}, count {len(devices)}")
    print(f"jax {jax.__version__}")
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.jax_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repository's src/ is not beside this script: {e}",
              file=sys.stderr)
        return 1
    print(f"compile cache: {enable_compile_cache()}")

    compile_s = [0.0]

    def on_duration(event, secs, **_):
        if event == COMPILE_EVENT:
            compile_s[0] += secs

    jax.monitoring.register_event_duration_secs_listener(on_duration)

    def gib(key):
        n = (dev.memory_stats() or {}).get(key)
        return f"{n / 2**30:.2f} GiB" if n is not None else "not reported"

    def phase_done(n, c0, t0):
        print(f"phase {n}: compile {compile_s[0] - c0:.1f}s, wall {time.time() - t0:.1f}s, "
              f"device memory in use {gib('bytes_in_use')}, peak so far "
              f"{gib('peak_bytes_in_use')}", flush=True)

    from repro.configs.registry import get_config

    cfg = get_config(ARCH)

    c0, t0 = compile_s[0], time.time()
    print("phase 1: kernel parity on the device, SmolLM-360M widths", flush=True)
    kernel_parity(cfg)
    phase_done(1, c0, t0)

    c0, t0 = compile_s[0], time.time()
    print("phase 2: served path, Pallas kernels on", flush=True)
    res = served_path(use_pallas=True)
    outs = [res.outputs[rid] for rid in range(REQUESTS)]
    check(len(res.metrics.completed) == REQUESTS and not res.metrics.failed,
          f"{len(res.metrics.completed)}/{REQUESTS} requests completed")
    check(all(len(o) == NEW_TOKENS for o in outs), "an output is short")
    check(all(0 <= t < cfg.vocab_size for o in outs for t in o), "a token id is out of vocab")
    print(f"  {len(outs)}/{REQUESTS} requests completed, each {NEW_TOKENS} tokens, "
          f"ids in [0, {cfg.vocab_size})")
    for name, eng in sorted(res.engines.items()):
        for prog, text in eng.lowered_programs().items():
            n = text.count("tpu_custom_call")
            print(f"  {name} {prog} program: {n} tpu_custom_call")
            check(n > 0, f"{name} {prog} program holds no Pallas kernel")
    del res
    gc.collect()
    phase_done(2, c0, t0)

    c0, t0 = compile_s[0], time.time()
    print("phase 3: the same prompts on the jnp path (information only)", flush=True)
    ref = served_path(use_pallas=False)
    agree = sum(a == b for rid, o in enumerate(outs) for a, b in zip(o, ref.outputs[rid]))
    first = sum(o[0] == ref.outputs[rid][0] for rid, o in enumerate(outs))
    print(f"  greedy tokens agreeing with the Pallas run: {agree}/{REQUESTS * NEW_TOKENS}; "
          f"first tokens {first}/{REQUESTS}")
    del ref
    gc.collect()
    phase_done(3, c0, t0)

    print(f"peak device memory: {gib('peak_bytes_in_use')}")
    print(f"compile total: {compile_s[0]:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
