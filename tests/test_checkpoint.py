"""Checkpoint/resume + profiling hooks.

Beyond-reference subsystem (the reference persists only strategy files,
SURVEY §5.4): full train-state round-trip through orbax and npz, resume
continuity, and the per-op profile hook.
"""

import numpy as np
import pytest

import flexflow_tpu as ff


def _small_model(batch=16):
    cfg = ff.FFConfig(batch_size=batch, compute_dtype="float32")
    m = ff.FFModel(cfg)
    inp = m.create_tensor((batch, 8), nchw=False)
    t = m.dense(inp, 16, activation="relu", name="fc1")
    t = m.dense(t, 4, name="fc2")
    m.softmax(t)
    m.compile(ff.SGDOptimizer(lr=0.1, momentum=0.9),
              ff.LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
              [ff.MetricsType.ACCURACY])
    m.init_layers(seed=3)
    return m, inp


def _feed(m, inp, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((16, 8), dtype=np.float32)
    y = rng.integers(0, 4, size=(16, 1), dtype=np.int32)
    m.set_batch({inp: x}, y)


def test_orbax_roundtrip_resume(devices, tmp_path):
    m, inp = _small_model()
    _feed(m, inp)
    for _ in range(3):
        m.train_iteration()
    m.sync()
    ckpt = str(tmp_path / "ckpt")
    m.save(ckpt)
    w_saved = m.get_parameter("fc1")
    step_saved = m._step_count

    # Diverge, then restore.
    for _ in range(2):
        m.train_iteration()
    m.sync()
    assert not np.allclose(m.get_parameter("fc1"), w_saved)
    m.load(ckpt)
    np.testing.assert_allclose(m.get_parameter("fc1"), w_saved)
    assert m._step_count == step_saved

    # Restored optimizer momentum: one more step must match a fresh model
    # restored to the same point taking the same step.
    _feed(m, inp, seed=1)
    m.train_iteration()
    m.sync()
    ref = m.get_parameter("fc1")

    m2, inp2 = _small_model()
    _feed(m2, inp2, seed=9)
    m2.train_iteration()  # builds opt state
    m2.sync()
    m2.load(ckpt)
    _feed(m2, inp2, seed=1)
    m2.train_iteration()
    m2.sync()
    np.testing.assert_allclose(m2.get_parameter("fc1"), ref, atol=1e-6)


def test_npz_roundtrip(devices, tmp_path):
    m, inp = _small_model()
    _feed(m, inp)
    m.train_iteration()
    m.sync()
    path = str(tmp_path / "weights.npz")
    m.save(path)
    w = m.get_parameter("fc2")
    for _ in range(2):
        m.train_iteration()
    m.sync()
    m.load(path)
    np.testing.assert_allclose(m.get_parameter("fc2"), w)


@pytest.mark.parametrize("form", ["npz", "orbax"])
def test_a_restore_leaves_the_step_one_program(devices, tmp_path, form):
    """What a restore puts back (parameters, batch-norm statistics, the
    optimizer's state) is placed as the step hands it back, so the step
    after it runs the program the steps before it ran."""
    cfg = ff.FFConfig(batch_size=8, compute_dtype="float32")
    cfg.parse_args(["-ll:tpu", "1"])
    m = ff.FFModel(cfg)
    inp = m.create_tensor((8, 3, 8, 8))
    t = m.batch_norm(m.conv2d(inp, 4, 3, 3, 1, 1, 1, 1, name="c0"), name="bn")
    m.softmax(m.dense(m.flat(t, name="flat"), 4, name="fc"), name="sm")
    m.compile(ff.SGDOptimizer(lr=0.1, momentum=0.9),
              ff.LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
              [ff.MetricsType.ACCURACY])
    m.init_layers(seed=3)
    rng = np.random.default_rng(0)
    m.set_batch({inp: rng.standard_normal((8, 8, 8, 3), dtype=np.float32)},
                rng.integers(0, 4, size=(8, 1), dtype=np.int32))
    assert m._stats  # the model has statistics to restore
    for _ in range(2):
        m.train_iteration()
    path = str(tmp_path / ("ck.npz" if form == "npz" else "ck"))
    m.save(path)
    m.load(path)
    for _ in range(2):
        m.train_iteration()
        assert m._train_step_fn._cache_size() == 1
    m.sync()


def test_checkpoint_manager_rotation(devices, tmp_path):
    from flexflow_tpu.runtime.checkpoint import CheckpointManager

    m, inp = _small_model()
    _feed(m, inp)
    mgr = CheckpointManager(str(tmp_path / "mgr"), max_to_keep=2)
    for _ in range(4):
        m.train_iteration()
        m.sync()
        mgr.save(m)
    mgr.wait_until_finished()
    step = m._step_count
    m.train_iteration()
    m.sync()
    restored = mgr.restore_latest(m)
    assert restored == step
    assert m._step_count == step
    mgr.close()


def test_op_profile_reports_all_ops(devices):
    m, inp = _small_model()
    prof = __import__("flexflow_tpu.runtime.profiling",
                      fromlist=["op_profile"]).op_profile(m, which="forward")
    assert set(prof) == {op.name for op in m.ops}
    assert all(v["forward_ms"] >= 0 for v in prof.values())


def test_pipeline_checkpoint_layout_portable(devices, tmp_path):
    """Checkpoints canonicalize the packed pipeline stage-weight buffer
    to per-op arrays, so a save from a pipelined model restores into a
    plain model and vice versa (elastic resume across layout changes)."""
    import flexflow_tpu as ff

    def build(pipeline):
        cfg = ff.FFConfig(batch_size=16)
        m = ff.FFModel(cfg)
        inp = m.create_tensor((16, 16), nchw=False, name="x")
        t = m.dense(inp, 32, activation="relu", name="fc1")
        t = m.dense(t, 24, activation="relu", name="fc2")
        t = m.dense(t, 10, name="fc3")
        m.softmax(t, name="sm")
        if pipeline:
            m.set_pipeline(num_stages=2, num_microbatches=4, dp_degree=2)
        m.compile(ff.SGDOptimizer(lr=0.05, momentum=0.9),
                  "sparse_categorical_crossentropy", ["accuracy"])
        m.init_layers(seed=3)
        return m, inp

    m, inp = build(True)
    if m._pipeline_plan is None:
        pytest.skip("pipeline not expressible on this mesh")
    rng = np.random.default_rng(0)
    x = rng.standard_normal((16, 16), dtype=np.float32)
    y = rng.integers(0, 10, size=(16, 1), dtype=np.int32)
    m.set_batch({inp: x}, y)
    m.train_iteration()
    m.sync()
    k1 = m.get_parameter("fc2", "kernel")
    p = str(tmp_path / "ckpt")
    m.save(p)

    # pipelined -> pipelined (packed buffer round-trips), resume trains
    m2, inp2 = build(True)
    m2.load(p)
    np.testing.assert_allclose(k1, m2.get_parameter("fc2", "kernel"),
                               rtol=1e-6)
    m2.set_batch({inp2: x}, y)
    m2.train_iteration()
    m2.sync()

    # pipelined -> plain (canonical per-op layout restores anywhere)
    m3, inp3 = build(False)
    m3.load(p)
    np.testing.assert_allclose(k1, m3.get_parameter("fc2", "kernel"),
                               rtol=1e-6)
    m3.set_batch({inp3: x}, y)
    m3.train_iteration()
    m3.sync()

    # plain -> pipelined (per-op arrays repack into the stage buffer)
    p2 = str(tmp_path / "ckpt2")
    m3.save(p2)
    m4, inp4 = build(True)
    m4.load(p2)
    np.testing.assert_allclose(m3.get_parameter("fc1", "kernel"),
                               m4.get_parameter("fc1", "kernel"), rtol=1e-6)
    m4.set_batch({inp4: x}, y)
    m4.train_iteration()
    m4.sync()
