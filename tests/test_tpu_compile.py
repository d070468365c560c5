"""Compile the serving path's Pallas kernels for a TPU v5e chip that is
described, not attached, at SmolLM-360M widths (15 query heads over 5 KV
heads, head_dim 64, page size 16, 1024-token rows, bf16 and int8 pools),
and the paged decode at Granite-8B's too (32 over 8, head_dim 128).

Interpret-mode tests cannot see what Mosaic refuses — unaligned blocks,
too much VMEM — so each case lowers the kernel with ``interpret=False``,
compiles it with the TPU compiler, and checks that the program holds the
Mosaic kernel (``tpu_custom_call``). Nothing runs; no chip is needed.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library at a time, and every xdist worker
imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention.kernel import decode_attention_grouped
from repro.kernels.flash_attention.kernel import flash_attention_bhsd
from repro.kernels.paged_attention.kernel import (
    paged_attention_grouped,
    paged_prefill_write_grouped,
    paged_prefill_write_grouped_quant,
)
from repro.kernels.rmsnorm import ops as rms_ops

# SmolLM-360M serving geometry
H, KV, HD, D = 15, 5, 64, 960
G = H // KV
PS, SEQ = 16, 1024
P = SEQ // PS              # block-table width
B = 8                      # the batch tier's decode slots
NUM_PAGES = 1 + B * P      # every slot at full length + the null page


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around these tests
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *args, one_chip):
    specs = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip) for a in args]
    return jax.jit(fn).lower(*specs).compile().as_text()


def _s(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


# Granite-8B's attention (32 query heads over 8 KV heads of 128), the batch
# tier's decode of the granite-8b-18l cell
GRANITE = dict(KV=8, G=4, HD=128)


@pytest.mark.parametrize("pool,widths", [
    pytest.param(pool, widths, id=pool if widths is None else f"granite-{pool}")
    for widths in (None, GRANITE) for pool in ("bf16", "int8", "chained")
])
def test_paged_decode_compiles(pool, widths, one_chip):
    kv, g, hd = (KV, G, HD) if widths is None else (widths["KV"], widths["G"], widths["HD"])
    dtype = jnp.int8 if pool == "int8" else jnp.bfloat16
    q = _s((B, kv, g, hd), jnp.bfloat16)
    pk = _s((NUM_PAGES, kv, PS, hd), dtype)
    lens = _s((B,), jnp.int32)
    if pool == "chained":
        tpp = PS                                   # table pages of 16 entries
        l1 = _s((B, P // tpp), jnp.int32)
        l2 = _s((1 + B * P // tpp, tpp), jnp.int32)
        fn = lambda q, k, v, t, n, l2: paged_attention_grouped(
            q, k, v, t, n, interpret=False, l2_tab=l2)
        args = (q, pk, pk, l1, lens, l2)
    elif pool == "int8":
        sc = _s((NUM_PAGES, kv, PS, 1), jnp.bfloat16)
        fn = lambda q, k, v, t, n, ks, vs: paged_attention_grouped(
            q, k, v, t, n, interpret=False, pool_ks=ks, pool_vs=vs)
        args = (q, pk, pk, _s((B, P), jnp.int32), lens, sc, sc)
    else:
        fn = lambda q, k, v, t, n: paged_attention_grouped(q, k, v, t, n, interpret=False)
        args = (q, pk, pk, _s((B, P), jnp.int32), lens)
    assert "tpu_custom_call" in _compiled_text(fn, *args, one_chip=one_chip)


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_paged_prefill_write_compiles(quant, one_chip):
    Lp = 256
    k = _s((1, Lp, KV, HD), jnp.bfloat16)
    row = _s((P,), jnp.int32)
    if quant:
        pool = _s((NUM_PAGES, KV, PS, HD), jnp.int8)
        sc = _s((NUM_PAGES, KV, PS, 1), jnp.bfloat16)
        fn = lambda pk, pv, ks, vs, k, v, t: paged_prefill_write_grouped_quant(
            pk, pv, ks, vs, k, v, t, interpret=False)
        args = (pool, pool, sc, sc, k, k, row)
    else:
        pool = _s((NUM_PAGES, KV, PS, HD), jnp.bfloat16)
        fn = lambda pk, pv, k, v, t: paged_prefill_write_grouped(pk, pv, k, v, t, interpret=False)
        args = (pool, pool, k, k, row)
    assert "tpu_custom_call" in _compiled_text(fn, *args, one_chip=one_chip)


def test_flash_attention_compiles(one_chip):
    S = 256
    q = _s((1, H, S, HD), jnp.bfloat16)
    kv = _s((1, KV, S, HD), jnp.bfloat16)
    fn = lambda q, k, v: flash_attention_bhsd(q, k, v, interpret=False)
    assert "tpu_custom_call" in _compiled_text(fn, q, kv, kv, one_chip=one_chip)


def test_decode_attention_compiles(one_chip):
    q = _s((B, KV, G, HD), jnp.bfloat16)
    kv = _s((B, KV, SEQ, HD), jnp.bfloat16)
    fn = lambda q, k, v, n: decode_attention_grouped(q, k, v, n, interpret=False)
    text = _compiled_text(fn, q, kv, kv, _s((B,), jnp.int32), one_chip=one_chip)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("rows", [3, 20])
def test_rmsnorm_odd_rows_compile(rows, one_chip, monkeypatch):
    """The decode batch normalises ``max_slots`` rows and speculative
    verify ``slots*(k+1)``: row counts that no power-of-two block divides
    must still give Mosaic an aligned block."""
    monkeypatch.setattr(rms_ops, "interpret", lambda: False)
    x = _s((rows, 1, D), jnp.bfloat16)
    w = _s((D,), jnp.bfloat16)
    assert "tpu_custom_call" in _compiled_text(rms_ops.rmsnorm, x, w, one_chip=one_chip)
