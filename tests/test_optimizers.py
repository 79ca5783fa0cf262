"""Optimizer updates vs. the reference kernel formulas in numpy.

Reference: sgd_update (optimizer_kernel.cu:23-40), adam_update (:206-225)
and the alpha_t schedule (optimizer.cc AdamOptimizer::next_epoch).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flexflow_tpu as ff
from flexflow_tpu.optimizers import AdamOptimizer, SGDOptimizer


def np_sgd(w, g, v, lr, wd, mom, nesterov):
    gt = g + wd * w
    if mom > 0:
        v = v * mom + gt
        gt = gt + mom * v if nesterov else v
    return w - lr * gt, v


def test_sgd_plain_and_momentum_and_nesterov():
    rng = np.random.default_rng(0)
    w = rng.standard_normal((5, 3), dtype=np.float32)
    g = rng.standard_normal((5, 3), dtype=np.float32)

    for mom, nest in [(0.0, False), (0.9, False), (0.9, True)]:
        opt = SGDOptimizer(lr=0.1, momentum=mom, nesterov=nest, weight_decay=1e-4)
        params = {"w": jnp.asarray(w)}
        state = opt.init_state(params)
        p1, s1 = opt.apply(params, {"w": jnp.asarray(g)}, state, opt.hparams())
        w_ref, v_ref = np_sgd(w, g, np.zeros_like(w), 0.1, 1e-4, mom, nest)
        np.testing.assert_allclose(np.asarray(p1["w"]), w_ref, rtol=1e-6, atol=1e-6)
        # second step exercises the momentum buffer
        g2 = rng.standard_normal((5, 3), dtype=np.float32)
        p2, s2 = opt.apply(p1, {"w": jnp.asarray(g2)}, s1, opt.hparams())
        w_ref2, v_ref2 = np_sgd(w_ref, g2, v_ref, 0.1, 1e-4, mom, nest)
        np.testing.assert_allclose(np.asarray(p2["w"]), w_ref2, rtol=1e-6, atol=1e-6)


def test_adam_matches_reference_formula():
    rng = np.random.default_rng(1)
    w = rng.standard_normal((7,), dtype=np.float32)
    opt = AdamOptimizer(alpha=1e-3, beta1=0.9, beta2=0.999, weight_decay=1e-4, epsilon=1e-8)
    params = {"w": jnp.asarray(w)}
    state = opt.init_state(params)

    m = np.zeros_like(w)
    v = np.zeros_like(w)
    w_ref = w.copy()
    for step in range(3):
        opt.next_epoch()  # reference advances schedule before updates
        g = rng.standard_normal((7,), dtype=np.float32)
        params, state = opt.apply(params, {"w": jnp.asarray(g)}, state, opt.hparams())
        b1t = 0.9 ** (step + 1)
        b2t = 0.999 ** (step + 1)
        alpha_t = 1e-3 * np.sqrt(1 - b2t) / (1 - b1t)
        gt = g + 1e-4 * w_ref
        m = 0.9 * m + 0.1 * gt
        v = 0.999 * v + 0.001 * gt * gt
        w_ref = w_ref - alpha_t * m / (np.sqrt(v) + 1e-8)
        np.testing.assert_allclose(np.asarray(params["w"]), w_ref, rtol=1e-5, atol=1e-6)


def test_optax_adapter_matches_builtin_sgd(devices):
    """OptaxOptimizer(optax.sgd(lr)) == built-in SGDOptimizer over
    several steps (same update rule, state riding the fused step)."""
    import optax

    def run(opt):
        cfg = ff.FFConfig(batch_size=16)
        m = ff.FFModel(cfg)
        inp = m.create_tensor((16, 8), nchw=False)
        t = m.dense(inp, 16, activation="relu", name="fc1")
        t = m.dense(t, 4, name="fc2")
        m.softmax(t, name="sm")
        m.compile(opt, "sparse_categorical_crossentropy", ["accuracy"])
        m.init_layers(seed=4)
        rng = np.random.default_rng(0)
        x = rng.standard_normal((16, 8), dtype=np.float32)
        y = rng.integers(0, 4, size=(16, 1), dtype=np.int32)
        m.set_batch({inp: x}, y)
        for _ in range(4):
            m.train_iteration()
        m.sync()
        return m.get_parameter("fc1", "kernel"), m

    k_ref, _ = run(ff.SGDOptimizer(lr=0.1))
    k_opx, _ = run(ff.OptaxOptimizer(optax.sgd(0.1)))
    np.testing.assert_allclose(k_ref, k_opx, rtol=1e-5, atol=1e-6)


def test_optax_adamw_trains_and_checkpoints(devices, tmp_path):
    """An optax chain (clip + adamw) trains, and its NamedTuple state
    survives a save/load round-trip and keeps training."""
    import optax

    def build():
        cfg = ff.FFConfig(batch_size=16)
        m = ff.FFModel(cfg)
        inp = m.create_tensor((16, 8), nchw=False)
        t = m.dense(inp, 32, activation="relu", name="fc1")
        t = m.dense(t, 4, name="fc2")
        m.softmax(t, name="sm")
        m.compile(ff.OptaxOptimizer(
            optax.chain(optax.clip_by_global_norm(1.0),
                        optax.adamw(1e-2))),
            "sparse_categorical_crossentropy", ["accuracy"])
        m.init_layers(seed=4)
        return m, inp

    m, inp = build()
    rng = np.random.default_rng(0)
    x = rng.standard_normal((16, 8), dtype=np.float32)
    y = np.argmax(x[:, :4], 1).astype(np.int32)[:, None]
    losses = []
    for _ in range(15):
        m.set_batch({inp: x}, y)
        m.train_iteration()
        m.sync()
        m.get_metrics()
        losses.append(m.last_loss)
        m.reset_metrics()
    assert losses[-1] < losses[0] * 0.5, losses

    # npz path explicitly: pins the NamedTuple rebuild + mesh
    # re-placement of the non-dict optax state (the orbax path would
    # otherwise shadow it in CI)
    p = str(tmp_path / "ckpt.npz")
    m.save(p)
    m2, inp2 = build()
    m2.load(p)
    np.testing.assert_allclose(m.get_parameter("fc1", "kernel"),
                               m2.get_parameter("fc1", "kernel"), rtol=1e-6)
    m2.set_batch({inp2: x}, y)
    m2.train_iteration()
    m2.sync()

    p2 = str(tmp_path / "ckpt_orbax")
    m.save(p2)
    m3, inp3 = build()
    m3.load(p2)
    np.testing.assert_allclose(m.get_parameter("fc1", "kernel"),
                               m3.get_parameter("fc1", "kernel"), rtol=1e-6)
    m3.set_batch({inp3: x}, y)
    m3.train_iteration()
    m3.sync()


@pytest.mark.parametrize("n_devices", [1, 8])
def test_optax_state_is_placed_as_the_step_returns_it(devices, n_devices):
    """A leaf optax makes from scratch (the step count) is committed to
    the mesh before the first step, on one device too: the step is one
    program through a drain and a reset."""
    import optax

    cfg = ff.FFConfig(batch_size=16)
    cfg.parse_args(["-ll:tpu", str(n_devices)])
    m = ff.FFModel(cfg)
    inp = m.create_tensor((16, 8), nchw=False)
    m.softmax(m.dense(inp, 4, name="fc"), name="sm")
    m.compile(ff.OptaxOptimizer(optax.adamw(1e-2)),
              "sparse_categorical_crossentropy", ["accuracy"])
    m.init_layers(seed=4)
    leaves = jax.tree.leaves(m._opt_state)
    assert all(a.committed and len(a.devices()) == n_devices for a in leaves)
    m.set_batch({inp: np.ones((16, 8), np.float32)},
                np.zeros((16, 1), np.int32))
    for i in range(4):
        m.train_iteration()
        assert m._train_step_fn._cache_size() == 1
        if i == 1:
            m.get_metrics()
        if i == 2:
            m.reset_metrics()


def test_optax_pipelined_checkpoint_portability(devices, tmp_path):
    """optax slot states nest params-shaped dicts inside NamedTuples;
    a pipelined model's packed '_pipe' buffer inside those nodes must
    canonicalize on save and repack on restore — including restoring
    into a PLAIN model (layout portability)."""
    import optax

    def build(pipeline):
        cfg = ff.FFConfig(batch_size=16)
        m = ff.FFModel(cfg)
        inp = m.create_tensor((16, 16), nchw=False, name="x")
        t = m.dense(inp, 32, activation="relu", name="fc1")
        t = m.dense(t, 24, activation="relu", name="fc2")
        t = m.dense(t, 4, name="fc3")
        m.softmax(t, name="sm")
        if pipeline:
            m.set_pipeline(num_stages=2, num_microbatches=4, dp_degree=2)
        m.compile(ff.OptaxOptimizer(optax.adamw(1e-2)),
                  "sparse_categorical_crossentropy", ["accuracy"])
        m.init_layers(seed=6)
        return m, inp

    m, inp = build(True)
    if m._pipe_pack() is None:
        import pytest
        pytest.skip("pipeline not expressible on this mesh")
    rng = np.random.default_rng(0)
    x = rng.standard_normal((16, 16), dtype=np.float32)
    y = rng.integers(0, 4, size=(16, 1), dtype=np.int32)
    m.set_batch({inp: x}, y)
    m.train_iteration()
    m.sync()
    p = str(tmp_path / "ckpt.npz")
    m.save(p)

    # packed -> packed
    m2, inp2 = build(True)
    m2.load(p)
    np.testing.assert_allclose(m.get_parameter("fc2", "kernel"),
                               m2.get_parameter("fc2", "kernel"), rtol=1e-6)
    m2.set_batch({inp2: x}, y)
    m2.train_iteration()
    m2.sync()

    # packed -> plain (canonical slot layout restores anywhere)
    m3, inp3 = build(False)
    m3.load(p)
    np.testing.assert_allclose(m.get_parameter("fc2", "kernel"),
                               m3.get_parameter("fc2", "kernel"), rtol=1e-6)
    m3.set_batch({inp3: x}, y)
    m3.train_iteration()
    m3.sync()
