"""The kernels of the DeepSeek-V2, dots3, MiMo-V2-Flash and Phi-4-mini-flash
cells at their real widths, compiled for a v5e that is described and not
attached: what the chip's compiler refuses (a block Mosaic cannot tile,
too much VMEM) costs no chip time.  Nothing runs, so nothing here is a
result or a time.  The topology is described inside a fixture, in this
one file, so that only the worker that runs these tests loads the TPU's
library."""

import base64
import hashlib
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from flexflow_tpu.kernels.dsa_index import index_scores
from flexflow_tpu.kernels.flash_attention import flash_attention, tiling
from flexflow_tpu.kernels.grouped_matmul import grouped_matmul
from flexflow_tpu.kernels.ssm_scan import selective_scan


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


@pytest.mark.parametrize("kv_heads,window,names", [
    (1, None, ("flash_fwd", "flash_dq", "flash_dkv")),
    (2, 128, ("flash_win_sink_fwd", "flash_win_dq", "flash_win_dkv"))])
def test_grouped_kernels_at_the_mimo_layers_heads(one_chip, kv_heads, window,
                                                  names):
    """MiMo-V2-Flash's share: 16 query heads of 192 | 128 over 1 sequence
    of 8192, reading one key/value head (full layers) or two, within a
    band of 128 keys and with a sink (window layers)."""
    def spec(heads, width):
        return jax.ShapeDtypeStruct((1, heads, 8192, width), jnp.bfloat16,
                                    sharding=one_chip)
    sink = [jax.ShapeDtypeStruct((16,), jnp.float32, sharding=one_chip)] \
        if window else []

    def f(q, k, v, *b):
        return jnp.sum(flash_attention(
            q, k, v, causal=True, scale=192 ** -0.5, window=window,
            sink=b[0] if b else None).astype(jnp.float32))
    compiled = jax.jit(jax.grad(f, argnums=tuple(range(3 + len(sink))))) \
        .lower(spec(16, 192), spec(kv_heads, 192), spec(kv_heads, 128),
               *sink).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 3
    assert all(n in text for n in names)


@pytest.mark.parametrize("window,head_dim", [(128, 192), (513, 256)])
def test_window_grids_hold_no_step_outside_the_band(window, head_dim):
    """MiMo-V2-Flash's and dots3's window layers at 8192 tokens: every
    step of a banded grid runs a body but for those of the blocks whose
    band the sequence clips (the forward's and dq's first q blocks,
    dkv's last k blocks), and the running tiles compute a bounded
    multiple of the band's pairs."""
    plans = tiling(8192, 8192, head_dim, True, window=window)
    b = plans["flash_win_dq"]["block_q"]
    assert b == plans["flash_win_dq"]["block_k"]
    n = 8192 // b
    edge = -(-(window - 1) // b)             # k blocks left of the diagonal
    for kernel, plan in plans.items():
        assert plan["grid_steps"] == n * (edge + 1), kernel
        clipped = edge * (edge + 1) // 2     # the corner the sequence cuts
        assert plan["grid_steps"] - plan["body_steps"] == clipped, kernel
        assert plan["band_pairs"] <= plan["pairs_computed"] \
            <= 2 * plan["band_pairs"], kernel


def _decoded(text):
    """Lowered text with each Mosaic kernel body decoded and printed
    without source locations (the body carries the kernel file's line
    numbers)."""
    from jax._src.interpreters import mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir
    ctx = mlir.make_ir_context()
    tpu.register_dialect(ctx)
    ctx.allow_unregistered_dialects = True

    def body(match):
        with ctx:
            module = ir.Module.parse(base64.b64decode(match.group(1)))
        return module.operation.get_asm(enable_debug_info=False)
    return re.sub(r"\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22", body, text)


# The plain causal and the selected kernels' gradients lower to the text
# they lowered to before the window kernels' grids were banded: sha256 of
# `_decoded` of the lowered text at the widths below (MiMo-V2-Flash's full
# layers, DeepSeek-V2's heads, dots3's full layers), computed with this
# function on a `git archive` of the commit before that change.
KERNEL_DIGESTS = {
    "plain-16-over-1": (
        (1, 16, 1, 192, 128, 8192), {},
        "64f172fa8456176e79a23532dd6804daa65e083cb560e591ea39e6c1a1f6d68a"),
    "plain-8-over-8": (
        (2, 8, 8, 192, 128, 4096), {},
        "584e4deaddd72f1e8e8e6868391d3acf38806baa3925439813e7607d9bbebd74"),
    "selected-4-over-4": (
        (1, 4, 4, 192, 128, 8192), dict(select=True),
        "bc28bfe86c8e108b0e0b1783d8c412427d48054069a98145d8d2e9fe948f48e6"),
}


@pytest.mark.parametrize("which", sorted(KERNEL_DIGESTS))
def test_other_kernels_are_the_programs_they_were(one_chip, which):
    (batch, heads, kv_heads, d, dv, seq), how, digest = KERNEL_DIGESTS[which]

    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    extra = [spec(batch, seq, seq)] if "select" in how else []

    def f(q, k, v, *sel):
        kw = dict(how, select=sel[0]) if sel else how
        return jnp.sum(flash_attention(q, k, v, causal=True, scale=d ** -0.5,
                                       **kw).astype(jnp.float32))
    text = jax.jit(jax.grad(f, argnums=(0, 1, 2))).lower(
        spec(batch, heads, seq, d), spec(batch, kv_heads, seq, d),
        spec(batch, kv_heads, seq, dv), *extra).as_text()
    assert hashlib.sha256(_decoded(text).encode()).hexdigest() == digest


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


def test_scan_kernels_at_the_mamba_layers_widths(one_chip):
    """Phi-4-mini-flash's Mamba layers: 1 sequence of 8192 tokens, 5120
    channels, a state of 16, x and Delta in bfloat16; forward and
    backward.  No buffer holds a state a token: nothing of 8192 x 5120 x
    16 elements or more, in any shape."""
    def spec(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def f(x, dt, a, b, c):
        return jnp.sum(selective_scan(x, dt, a, b, c).astype(jnp.float32))
    compiled = jax.jit(jax.grad(f, argnums=range(5))).lower(
        spec(1, 8192, 5120), spec(1, 8192, 5120),
        spec(5120, 16, dtype=jnp.float32), spec(1, 8192, 16,
                                                dtype=jnp.float32),
        spec(1, 8192, 16, dtype=jnp.float32)).compile()
    text = compiled.as_text()
    assert "ssm_scan_fwd" in text and "ssm_scan_bwd" in text
    sizes = [math.prod(int(d) for d in dims.split(",") if d)
             for dims in re.findall(r"[a-z0-9]+\[([0-9,]*)\]", text)]
    assert max(sizes) < 8192 * 5120 * 16
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30


@pytest.mark.parametrize("window,names", [
    (None, ("flash_fwd", "flash_dq", "flash_dkv")),
    (512, ("flash_win_fwd", "flash_win_dq", "flash_win_dkv"))])
def test_grouped_kernels_at_the_phi4_flash_heads(one_chip, window, names):
    """Phi-4-mini-flash's attention: 40 query heads of 64 over 20
    key/value heads, 1 sequence of 8192, causal (the full and cross
    layers) or within a band of 512 keys (the window layers)."""
    def spec(heads):
        return jax.ShapeDtypeStruct((1, heads, 8192, 64), jnp.bfloat16,
                                    sharding=one_chip)

    def f(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, scale=0.125,
                                       window=window).astype(jnp.float32))
    text = jax.jit(jax.grad(f, argnums=(0, 1, 2))).lower(
        spec(40), spec(20), spec(20)).compile().as_text()
    assert text.count("tpu_custom_call") >= 3
    assert all(n in text for n in names)
