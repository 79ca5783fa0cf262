"""Operations and bytes the benchmark's models need, from shapes alone.

The formulas are found by name: a configuration file names its training
formula under `flops`, a roofline metric names its kernel formula under
`formula`.  Each takes the configuration's `builder_kwargs` and returns
numbers per sample.  They are the benchmark's own: `mfu` and every
`*_roofline_share` divide by these, never by the program's
`op.flops_per_sample()`, which a later PR may change.

A multiply-add is two operations.  Training counts forward, the gradient
of the inputs and the gradient of the weights, three times forward; what
a kernel recomputes is not counted.
"""

ACT_BYTES = 2    # activations in bfloat16
PARAM_BYTES = 4  # parameters and their gradients in float32


def _out(size, kernel, stride, pad):
    return 1 + (size + 2 * pad - kernel) // stride


def alexnet_layers(height=229, width=229, num_classes=10, **_):
    """[(name, kind, forward FLOPs, input elems, weight elems, output
    elems)] per sample of examples/cpp/AlexNet/alexnet.cc."""
    convs = [("conv1", 64, 11, 4, 2, True), ("conv2", 192, 5, 1, 2, True),
             ("conv3", 384, 3, 1, 1, False), ("conv4", 256, 3, 1, 1, False),
             ("conv5", 256, 3, 1, 1, True)]
    h, w, c = height, width, 3
    rows = []
    for name, oc, k, s, p, pool in convs:
        oh, ow = _out(h, k, s, p), _out(w, k, s, p)
        rows.append((name, "conv", 2.0 * oh * ow * oc * k * k * c,
                     h * w * c, k * k * c * oc, oh * ow * oc))
        h, w, c = oh, ow, oc
        if pool:
            h, w = _out(h, 3, 2, 0), _out(w, 3, 2, 0)
    d = h * w * c
    for name, od in (("fc1", 4096), ("fc2", 4096), ("fc3", num_classes)):
        rows.append((name, "dense", 2.0 * d * od, d, d * od, od))
        d = od
    return rows


def alexnet_forward(**kw):
    return sum(r[2] for r in alexnet_layers(**kw))


def alexnet_train(**kw):
    """FLOPs per sample of one training step: 3 x forward (the usual
    count; conv1's input gradient, 3.4 % of it, is not needed and is
    counted all the same, as the issue's 4.28 GFLOP does)."""
    return 3.0 * alexnet_forward(**kw)


def alexnet_matmuls(batch, **kw):
    """(FLOPs, bytes) per step that the convolutions and dense layers
    need at `batch` samples: forward, weight gradient and, past the
    first layer, input gradient.  These are the operations XLA's TPU
    backend runs as 'convolution fusion's (a dot is a convolution
    there)."""
    flops = nbytes = 0.0
    for i, (_, _, f, xin, wts, out) in enumerate(alexnet_layers(**kw)):
        passes = 2 if i == 0 else 3
        flops += passes * f * batch
        fwd = (xin + out) * batch * ACT_BYTES + wts * PARAM_BYTES
        wgrad = (xin + out) * batch * ACT_BYTES + wts * PARAM_BYTES
        dgrad = (xin + out) * batch * ACT_BYTES + wts * PARAM_BYTES
        nbytes += fwd + wgrad + (dgrad if i else 0.0)
    return flops, nbytes


def transformer_matmul_params(num_layers, embed_dim, vocab_size,
                              mlp_ratio=4, **_):
    per_layer = (4 + 2 * mlp_ratio) * embed_dim * embed_dim
    return num_layers * per_layer + embed_dim * vocab_size


def causal_attention_forward(seq_length, embed_dim, **_):
    """FLOPs per sample and layer of causal attention's two matrix
    products, forward: QK^T and PV are 2*S*S*E each, half of it masked."""
    return 2.0 * seq_length * seq_length * embed_dim


def transformer_train(seq_length, num_layers, **kw):
    """FLOPs per sample (one sequence) of one training step: 6 x matmul
    parameters per token, plus causal attention forward and twice that
    backward.  Embedding lookups, LayerNorm, GELU and the softmax are
    not counted; the flash kernel's recomputation is not counted."""
    dense = 6.0 * transformer_matmul_params(num_layers=num_layers, **kw)
    attn = 3.0 * causal_attention_forward(seq_length, **kw) * num_layers
    return dense * seq_length + attn


def causal_attention_train(batch, seq_length, num_layers, embed_dim, **_):
    """(FLOPs, bytes) per step of causal attention, forward and
    backward, over all layers: what the flash kernels are given to do.
    Forward reads q, k, v and writes o; backward reads q, k, v, o, do
    and writes dq, dk, dv; the row statistics are left out."""
    flops = 3.0 * causal_attention_forward(seq_length, embed_dim) \
        * num_layers * batch
    tensor = batch * seq_length * embed_dim * ACT_BYTES
    return flops, (4 + 8) * tensor * num_layers
