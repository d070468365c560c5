"""Multi-device SPMD tests — run in a subprocess with 8 forced host devices
(the main pytest process must keep seeing 1 device)."""
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]


def run_in_subprocess(body: str):
    script = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        """
    ) + textwrap.dedent(body)
    r = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True,
        text=True,
        env={**__import__("os").environ, "PYTHONPATH": str(REPO / "src")},
        timeout=600,
    )
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    return r.stdout


def test_moe_shard_map_matches_single_device():
    run_in_subprocess(
        """
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,)*2)
        from repro.models import MoECfg, ModelConfig
        from repro.models.common import init_tree
        from repro.models.moe import moe_defs, moe_ffn
        from repro.sharding.axes import make_ctx
        cfg = ModelConfig(name="m", family="moe", n_layers=1, d_model=16, n_heads=2,
            n_kv_heads=2, d_ff=32, vocab_size=64,
            moe=MoECfg(n_experts=4, top_k=2, capacity_factor=100.0),
            param_dtype=jnp.float32, compute_dtype=jnp.float32)
        p = init_tree(jax.random.PRNGKey(0), moe_defs(cfg), jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 6, 16), jnp.float32)
        ref, aux_ref = moe_ffn(cfg, None, p, x)   # single-device oracle
        ctx = make_ctx(mesh)
        out, aux = jax.jit(lambda p, x: moe_ffn(cfg, ctx, p, x))(p, x)
        err = float(jnp.max(jnp.abs(out - ref)))
        assert err < 1e-4, err
        # aux is the mean of per-shard balance losses (standard DP form);
        # it approximates but does not equal the whole-batch estimator.
        assert abs(float(aux - aux_ref)) / float(aux_ref) < 0.5
        print("MOE_SHARD_OK", err)
        """
    )


def test_moe_fsdp_expert_gather_matches():
    run_in_subprocess(
        """
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,)*2)
        from repro.models import MoECfg, ModelConfig
        from repro.models.common import init_tree
        from repro.models.moe import moe_defs, moe_ffn
        from repro.sharding.axes import make_ctx
        cfg = ModelConfig(name="m", family="moe", n_layers=1, d_model=16, n_heads=2,
            n_kv_heads=2, d_ff=32, vocab_size=64,
            moe=MoECfg(n_experts=4, top_k=1, capacity_factor=100.0, fsdp_experts=True),
            param_dtype=jnp.float32, compute_dtype=jnp.float32)
        p = init_tree(jax.random.PRNGKey(0), moe_defs(cfg), jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 6, 16), jnp.float32)
        ref, _ = moe_ffn(cfg.replace(moe=MoECfg(n_experts=4, top_k=1,
            capacity_factor=100.0, fsdp_experts=False)), None, p, x)
        ctx = make_ctx(mesh)
        out, _ = jax.jit(lambda p, x: moe_ffn(cfg, ctx, p, x))(p, x)
        err = float(jnp.max(jnp.abs(out - ref)))
        assert err < 1e-4, err
        print("MOE_FSDP_OK", err)
        """
    )


def test_compressed_allreduce_close_to_psum():
    run_in_subprocess(
        """
        mesh = jax.make_mesh((8,), ("pod",), axis_types=(jax.sharding.AxisType.Auto,))
        from repro.train.compression import make_compressed_psum
        x = jax.random.normal(jax.random.PRNGKey(0), (8, 256), jnp.float32)
        sh = NamedSharding(mesh, P("pod", None))
        xs = jax.device_put(x, sh)
        fn = make_compressed_psum(mesh, "pod", P("pod", None))
        out = jax.jit(fn)(xs)                # per-shard rows each all-reduced
        want = jnp.broadcast_to(x.sum(0, keepdims=True), x.shape)
        rel = float(jnp.max(jnp.abs(out - want)) / jnp.max(jnp.abs(want)))
        assert rel < 0.05, rel               # int8 wire error bound
        print("COMPRESS_OK", rel)
        """
    )


def test_elastic_remesh_restore():
    run_in_subprocess(
        """
        import tempfile
        from repro.train import checkpoint as ckpt
        from jax.sharding import Mesh
        # save under a (4,2) mesh sharding, restore under (2,4)
        t = {"w": jnp.arange(64.0).reshape(8, 8)}
        d = tempfile.mkdtemp()
        mesh_a = jax.make_mesh((4, 2), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,)*2)
        sh_a = {"w": NamedSharding(mesh_a, P("data", "model"))}
        placed = jax.tree.map(lambda x, s: jax.device_put(x, s), t, sh_a)
        ckpt.save(d, 1, placed)
        mesh_b = jax.make_mesh((2, 4), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,)*2)
        sh_b = {"w": NamedSharding(mesh_b, P("data", "model"))}
        back = ckpt.restore(d, 1, t, shardings=sh_b)
        assert jnp.array_equal(back["w"], t["w"])
        assert back["w"].sharding.mesh.shape == mesh_b.shape
        print("REMESH_OK")
        """
    )


def test_small_mesh_train_step_executes():
    """Actually RUN (not just compile) a sharded train step on 8 devices."""
    run_in_subprocess(
        """
        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,)*2)
        from repro.configs.registry import get_config
        from repro.models import get_model
        from repro.sharding.axes import make_ctx
        from repro.launch.steps import make_train_step, param_shardings, opt_shardings, batch_shardings
        from repro.train.optimizer import OptConfig, init_opt_state
        cfg = get_config("glm4-9b", smoke=True).replace(
            d_model=64, n_heads=4, n_kv_heads=2, d_ff=128, attn_chunk=8, ce_chunks=2)
        model = get_model(cfg)
        ctx = make_ctx(mesh)
        params = model.init(jax.random.PRNGKey(0))
        ocfg = OptConfig(lr=1e-3)
        opt = init_opt_state(params, ocfg)
        psh = param_shardings(model, ctx, fsdp=True)
        osh = opt_shardings(model, ctx, ocfg)
        params = jax.tree.map(lambda x, s: jax.device_put(x, s), params, psh)
        opt = jax.tree.map(lambda x, s: jax.device_put(x, s) if s is not None else x, opt, osh)
        B, S = 4, 16
        batch = {"tokens": jnp.zeros((B, S), jnp.int32), "labels": jnp.ones((B, S), jnp.int32)}
        step = jax.jit(make_train_step(model, ctx, ocfg), donate_argnums=(0, 1))
        params, opt, metrics = step(params, opt, batch)
        l0 = float(metrics["loss"])
        for _ in range(3):
            params, opt, metrics = step(params, opt, batch)
        assert np.isfinite(l0) and float(metrics["loss"]) < l0
        print("TRAIN_SPMD_OK", l0, float(metrics["loss"]))
        """
    )


def test_moe_token_gather_matches_weight_gather():
    """The 104x llama4-decode optimization must be semantics-preserving."""
    run_in_subprocess(
        """
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,)*2)
        from repro.models import MoECfg, ModelConfig
        from repro.models.common import init_tree
        from repro.models.moe import moe_defs, moe_ffn
        from repro.sharding.axes import make_ctx
        base = ModelConfig(name="m", family="moe", n_layers=1, d_model=16, n_heads=2,
            n_kv_heads=2, d_ff=32, vocab_size=64,
            moe=MoECfg(n_experts=4, top_k=1, capacity_factor=100.0, fsdp_experts=True),
            param_dtype=jnp.float32, compute_dtype=jnp.float32)
        p = init_tree(jax.random.PRNGKey(0), moe_defs(base), jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 2, 16), jnp.float32)
        ctx = make_ctx(mesh)
        out_w, _ = jax.jit(lambda p, x: moe_ffn(base, ctx, p, x))(p, x)
        tok = base.replace(moe_token_gather=True)
        out_t, _ = jax.jit(lambda p, x: moe_ffn(tok, ctx, p, x))(p, x)
        err = float(jnp.max(jnp.abs(out_w - out_t)))
        assert err < 1e-4, err
        print("MOETOK_OK", err)
        """
    )


def test_seq_shard_activations_matches_baseline():
    """Megatron-SP variant must not change the math."""
    run_in_subprocess(
        """
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,)*2)
        from repro.configs.registry import get_config
        from repro.models import get_model
        from repro.sharding.axes import make_ctx
        cfg = get_config("granite-8b", smoke=True).replace(
            d_model=64, n_heads=4, n_kv_heads=2, attn_chunk=8, ce_chunks=2)
        ctx = make_ctx(mesh)
        model = get_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        batch = {"tokens": jnp.zeros((4, 16), jnp.int32),
                 "labels": jnp.ones((4, 16), jnp.int32)}
        l0, _ = jax.jit(lambda p, b: model.loss(ctx, p, b))(params, batch)
        sp = get_model(cfg.replace(seq_shard_activations=True))
        l1, _ = jax.jit(lambda p, b: sp.loss(ctx, p, b))(params, batch)
        assert abs(float(l0) - float(l1)) < 1e-4, (float(l0), float(l1))
        print("SP_OK", float(l0))
        """
    )
