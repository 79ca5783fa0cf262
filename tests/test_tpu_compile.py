"""The kernels of the DeepSeek-V2 and dots3 cells at their real widths, compiled for a
v5e that is described and not attached: what the chip's compiler refuses
(a block Mosaic cannot tile, too much VMEM) costs no chip time.  Nothing
runs, so nothing here is a result or a time.  The topology is described
inside a fixture, in this one file, so that only the worker that runs
these tests loads the TPU's library."""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from flexflow_tpu.kernels.dsa_index import index_scores
from flexflow_tpu.kernels.flash_attention import flash_attention
from flexflow_tpu.kernels.grouped_matmul import grouped_matmul


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip is written to the
    # persistent cache and cannot be read back without one
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()     # one that is initialised stays on
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def _custom_calls(compiled):
    return compiled.as_text().count("tpu_custom_call")


def test_flash_kernels_at_latent_attention_heads(one_chip):
    """2 sequences of 4096, 8 heads, query/key 192 and value 128."""
    def spec(d):
        return jax.ShapeDtypeStruct((2, 8, 4096, d), jnp.bfloat16,
                                    sharding=one_chip)

    def f(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       scale=0.1147).astype(jnp.float32))
    compiled = jax.jit(jax.grad(f, argnums=(0, 1, 2))).lower(
        spec(192), spec(192), spec(128)).compile()
    assert _custom_calls(compiled) >= 3        # forward, dq, dkv


def _grads_compile(one_chip, heads, d, dv, **how):
    """1 sequence of 8192: forward, dq and dkv of a call with ``how``."""
    def spec(width):
        return jax.ShapeDtypeStruct((1, heads, 8192, width), jnp.bfloat16,
                                    sharding=one_chip)
    extra = [jax.ShapeDtypeStruct((1, 8192, 8192), jnp.bfloat16,
                                  sharding=one_chip)] if "select" in how else []

    def f(q, k, v, *sel):
        kw = dict(how, select=sel[0]) if sel else how
        return jnp.sum(flash_attention(q, k, v, causal=True, scale=d ** -0.5,
                                       **kw).astype(jnp.float32))
    compiled = jax.jit(jax.grad(f, argnums=(0, 1, 2))).lower(
        spec(d), spec(d), spec(dv), *extra).compile()
    return compiled.as_text()


def test_window_kernels_at_the_window_layers_heads(one_chip):
    """dots3's window layers: 2 heads of 256 | 128, 513 keys a query."""
    text = _grads_compile(one_chip, 2, 256, 128, window=513)
    assert text.count("tpu_custom_call") >= 3
    assert "flash_win_fwd" in text and "flash_win_dkv" in text


def test_selected_kernels_at_the_full_layers_heads(one_chip):
    """dots3's full layers: 4 heads of 192 | 128 under a bfloat16
    selection of 8192 x 8192 (compared in float32: the v5e compares no
    bfloat16, which the interpreter does not mind)."""
    text = _grads_compile(one_chip, 4, 192, 128, select=True)
    assert text.count("tpu_custom_call") >= 3
    assert "flash_sel_fwd" in text and "flash_sel_dq" in text


def test_index_kernels_at_the_full_layers_index(one_chip):
    """dots3's index: 64 heads of 128 over 1 sequence of 8192, the first
    64 of a head turned in the kernels, float32 scores at the highest
    precision and their gradient from bfloat16 operands.  Nothing as long
    as a (heads x queries, keys) product is left in HBM: XLA's blocks
    held 3.3 GB of such temporaries (PERF.md, PR 35)."""
    def spec(*shape, lead=(1, 8192)):
        return jax.ShapeDtypeStruct(lead + shape, jnp.float32,
                                    sharding=one_chip)

    def f(q, k, w, g, cos, sin):
        return jnp.sum(index_scores(q, k, w, (cos, sin), jnp.bfloat16) * g)
    compiled = jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2))).lower(
        spec(64, 128), spec(128), spec(64), spec(8192),
        spec(32, lead=(8192,)), spec(32, lead=(8192,))).compile()
    text = compiled.as_text()
    assert "dsa_index_fwd" in text and "dsa_index_bwd" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30


@pytest.mark.parametrize("k,n", [(5120, 1536), (1536, 5120)])
def test_grouped_products_at_expert_widths(one_chip, k, n):
    """The budget's buffer of the cell: 24 + 10 tiles of 128 rows, 10
    experts, float32 weights, bfloat16 rows; up and down projection."""
    tiles = 34
    x = jax.ShapeDtypeStruct((tiles * 128, k), jnp.bfloat16,
                             sharding=one_chip)
    w = jax.ShapeDtypeStruct((10, k, n), jnp.float32, sharding=one_chip)
    tg = jax.ShapeDtypeStruct((tiles,), jnp.int32, sharding=one_chip)

    def f(x, w, tg):
        return jnp.sum(jnp.sin(grouped_matmul(x, w, tg, tile_m=128)
                               .astype(jnp.float32)))
    compiled = jax.jit(jax.value_and_grad(f, argnums=(0, 1))).lower(
        x, w, tg).compile()
    assert _custom_calls(compiled) >= 3        # gmm, gmm_t, tgmm
