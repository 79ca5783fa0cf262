"""Ring attention / Ulysses sequence parallelism vs dense attention.

Reference has no sequence parallelism (SURVEY §5.7); these tests pin the
TPU-native design: sequence-sharded attention over a ring of devices must
be numerically identical to dense attention over the gathered sequence,
forward and backward, causal and not.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from flexflow_tpu.kernels.flash_attention import mha_reference
from flexflow_tpu.parallel.sequence import (
    blockwise_attention,
    sequence_parallel_attention,
)

B, H, S, D = 2, 4, 64, 16


def _qkv(seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.float32)
    return mk(), mk(), mk()


def test_blockwise_matches_dense():
    q, k, v = _qkv()
    out, _ = blockwise_attention(q, k, v)
    ref = mha_reference(q, k, v)
    np.testing.assert_allclose(out, ref, atol=1e-5)


def test_blockwise_causal_offsets():
    q, k, v = _qkv(1)
    # Merge of two k-blocks with offsets == causal dense over full k.
    ref = mha_reference(q, k, v, causal=True)
    half = S // 2
    from flexflow_tpu.parallel.sequence import _merge_partials
    o1, l1 = blockwise_attention(q, k[:, :, :half], v[:, :, :half],
                                 causal=True, q_offset=0, k_offset=0)
    o2, l2 = blockwise_attention(q, k[:, :, half:], v[:, :, half:],
                                 causal=True, q_offset=0, k_offset=half)
    out, _ = _merge_partials(o1, l1, o2, l2)
    np.testing.assert_allclose(out, ref, atol=1e-5)


@pytest.mark.slow
@pytest.mark.parametrize("mode", ["ring", "ulysses"])
@pytest.mark.parametrize("causal", [False, True])
def test_sequence_parallel_matches_dense(devices, mode, causal):
    if mode == "ulysses" and causal:
        pytest.skip("ulysses+causal covered by ring; local attention is causal-safe only when aligned")
    mesh = Mesh(np.array(devices).reshape(2, 4), ("dp", "sp"))
    q, k, v = _qkv(2)
    out = sequence_parallel_attention(q, k, v, mesh, "sp", batch_axes="dp",
                                      causal=causal, mode=mode)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_ring_attention_grads_match(devices):
    mesh = Mesh(np.array(devices).reshape(2, 4), ("dp", "sp"))
    q, k, v = _qkv(3)

    def loss_ring(q, k, v):
        o = sequence_parallel_attention(q, k, v, mesh, "sp", batch_axes="dp",
                                        causal=True)
        return jnp.sum(o * o)

    def loss_ref(q, k, v):
        o = mha_reference(q, k, v, causal=True)
        return jnp.sum(o * o)

    g = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-3)


def test_ring_with_flash_kernel_interpret(devices):
    """The ring's flash-kernel path (what runs on a real pod), with the
    pallas kernel in interpret mode on the CPU mesh."""
    mesh = Mesh(np.array(devices).reshape(2, 4), ("dp", "sp"))
    q, k, v = _qkv(5)
    for causal in (False, True):
        out = sequence_parallel_attention(q, k, v, mesh, "sp", batch_axes="dp",
                                          causal=causal, use_flash=True,
                                          interpret=True)
        ref = mha_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_ulysses_requires_divisible_heads(devices):
    mesh = Mesh(np.array(devices).reshape(2, 4), ("dp", "sp"))
    q, k, v = _qkv(4)
    out = sequence_parallel_attention(q, k, v, mesh, "sp", batch_axes="dp",
                                      mode="ulysses")
    assert out.shape == (B, H, S, D)
