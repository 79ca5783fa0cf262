"""Row-sparse host-resident embedding tables.

Reference: src/ops/embedding.cc:18-77 — the CPU embedding tasks touch
only the batch's rows of a host-zero-copy table; dlrm_strategy_hetero.cc
places 8x1M-row DLRM tables in host ZC memory.  Under test here: a
host-placed Embedding under plain SGD keeps its table host-side as
numpy, per-step transfer scales with the BATCH (u_max rows), not the
table, and training matches the dense device run bit-for-bit.
"""

import numpy as np
import pytest

import flexflow_tpu as ff
from flexflow_tpu.config import DeviceType


def _build(offload: bool, rows: int = 1000, momentum: float = 0.0,
           sparse=None, batch: int = 16, grad_accum: int = 1, seed: int = 11,
           fused: bool = False):
    cfg = ff.FFConfig(batch_size=batch, grad_accum_steps=grad_accum,
                      fused_optimizer=fused)
    cfg.sparse_host_embeddings = sparse
    if offload:
        cfg.strategies["emb"] = ff.ParallelConfig(
            DeviceType.CPU, (1, 1), (0,))
    m = ff.FFModel(cfg)
    ids = m.create_tensor((batch, 4), dtype="int32", name="ids")
    t = m.embedding(ids, rows, 8, name="emb")
    t = m.dense(t, 4, name="head")
    m.softmax(t, name="sm")
    m.compile(ff.SGDOptimizer(m, lr=0.1, momentum=momentum),
              ff.LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
              [ff.MetricsType.ACCURACY])
    m.init_layers(seed=seed)
    rng = np.random.default_rng(0)
    x = rng.integers(0, rows, (batch, 4)).astype(np.int32)
    y = (x[:, 0] % 4).astype(np.int32).reshape(-1, 1)
    m.set_batch({ids: x}, y)
    return m


def test_sparse_table_is_host_numpy(devices):
    m = _build(offload=True)
    assert "emb" in m._host_embed
    assert isinstance(m._params["emb"]["weight"], np.ndarray)
    # registered instead of the full-streaming path
    assert ("emb", "weight") not in m._offload


def test_sparse_training_matches_dense(devices):
    m_dev = _build(offload=False)
    m_host = _build(offload=True)
    assert "emb" in m_host._host_embed
    # identical init (threefry streams are platform-independent)
    np.testing.assert_array_equal(m_dev.get_parameter("emb", "weight"),
                                  m_host.get_parameter("emb", "weight"))
    for _ in range(8):
        m_dev.train_iteration()
        m_host.train_iteration()
    m_dev.sync()
    m_host.sync()
    np.testing.assert_allclose(m_dev.get_parameter("emb", "weight"),
                               m_host.get_parameter("emb", "weight"),
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(m_dev.get_parameter("head", "kernel"),
                               m_host.get_parameter("head", "kernel"),
                               rtol=2e-5, atol=2e-6)
    # the table is STILL host-resident numpy after training
    assert isinstance(m_host._params["emb"]["weight"], np.ndarray)


def test_transfer_scales_with_batch_not_table(devices):
    """The device-side leaf fed into the step is (u_max, D) where u_max
    derives from the BATCH's index count — growing the table leaves the
    per-step transfer unchanged."""
    m_small = _build(offload=True, rows=500)
    m_large = _build(offload=True, rows=50_000)
    u_small = m_small._host_embed["emb"]["u_max"]
    u_large = m_large._host_embed["emb"]["u_max"]
    assert u_small == u_large  # batch-driven, not table-driven
    assert u_large * 8 < 50_000  # far below table row count
    p_in, _, batch_in, ctxs = m_large._host_embed_swap_in(
        m_large._params, m_large._opt_state, m_large._batch)
    u_hwm = m_large._host_embed["emb"]["u_hwm"]
    assert u_hwm <= u_large  # adaptive bucket never exceeds the cap
    assert p_in["emb"]["weight"].shape == (u_hwm, 8)
    m_large.train_iteration()
    m_large.sync()


def test_untouched_rows_do_not_move(devices):
    m = _build(offload=True, rows=1000)
    before = m.get_parameter("emb", "weight").copy()
    m.train_iteration()
    m.sync()
    after = m.get_parameter("emb", "weight")
    touched = np.unique(np.asarray(m._host_idx["in_0"]
                                   if "in_0" in m._host_idx else
                                   next(iter(m._host_idx.values()))))
    untouched = np.setdiff1d(np.arange(1000), touched)
    assert untouched.size > 0
    np.testing.assert_array_equal(before[untouched], after[untouched])
    # and at least one touched row moved
    assert np.abs(after[touched] - before[touched]).max() > 0


def test_momentum_defaults_to_streaming(devices):
    """Auto mode must NOT go sparse when the update rule touches every
    row (SGD momentum decays untouched rows' buffers)."""
    m = _build(offload=True, momentum=0.9)
    assert "emb" not in m._host_embed
    assert ("emb", "weight") in m._offload


def test_forced_sparse_with_momentum_is_lazy(devices):
    """sparse_host_embeddings=True opts into lazy per-touched-row
    momentum (torch SparseAdam-style): still trains, table stays host."""
    m = _build(offload=True, momentum=0.9, sparse=True)
    assert "emb" in m._host_embed
    assert isinstance(m._opt_state["v"]["emb"]["weight"], np.ndarray)
    for _ in range(3):
        m.train_iteration()
    m.sync()
    assert isinstance(m._params["emb"]["weight"], np.ndarray)


def test_sparse_checkpoint_roundtrip(tmp_path, devices):
    m = _build(offload=True)
    for _ in range(2):
        m.train_iteration()
    m.sync()
    w = m.get_parameter("emb", "weight").copy()
    path = str(tmp_path / "ck.npz")
    from flexflow_tpu.runtime.checkpoint import (load_checkpoint,
                                                 save_checkpoint)
    save_checkpoint(m, path)
    m2 = _build(offload=True)
    load_checkpoint(m2, path)
    np.testing.assert_array_equal(w, m2.get_parameter("emb", "weight"))
    # restored table is still host-resident numpy
    assert isinstance(m2._params["emb"]["weight"], np.ndarray)
    m2.train_iteration()
    m2.sync()


def test_adaptive_bucket_with_repeated_keys(devices):
    """Skewed key distributions (few unique ids — the DLRM norm) pay a
    small power-of-two bucket on the wire, not the all-unique worst
    case; the bucket grows monotonically to its high-water mark and
    never shrinks (no retrace thrash)."""
    cfg = ff.FFConfig(batch_size=16)
    cfg.strategies["emb"] = ff.ParallelConfig(DeviceType.CPU, (1, 1), (0,))
    m = ff.FFModel(cfg)
    ids = m.create_tensor((16, 4), dtype="int32", name="ids")
    t = m.embedding(ids, 1000, 8, name="emb")
    t = m.dense(t, 4, name="head")
    m.softmax(t, name="sm")
    m.compile(ff.SGDOptimizer(m, lr=0.1),
              ff.LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
              [ff.MetricsType.ACCURACY])
    m.init_layers(seed=3)
    info = m._host_embed["emb"]
    y = np.zeros((16, 1), np.int32)
    x_skew = (np.arange(64).reshape(16, 4) % 5).astype(np.int32)  # 5 ids
    m.set_batch({ids: x_skew}, y)
    p_in, _, _, _ = m._host_embed_swap_in(m._params, m._opt_state, m._batch)
    assert info["u_max"] == 64          # all-unique worst case
    assert info["u_hwm"] == 8           # bucket for 5 uniques
    assert p_in["emb"]["weight"].shape == (8, 8)
    m.train_iteration()
    m.sync()
    # a more-unique batch grows the bucket...
    x_full = np.arange(64).reshape(16, 4).astype(np.int32)
    m.set_batch({ids: x_full}, y)
    m.train_iteration()
    m.sync()
    assert info["u_hwm"] == 64
    # ...and a skewed batch afterwards does NOT shrink it back
    m.set_batch({ids: x_skew}, y)
    m.train_iteration()
    m.sync()
    assert info["u_hwm"] == 64
    # actual unique counts are accounted for reporting
    assert info["uniq_rows_steps"] >= 3
    assert info["uniq_rows_total"] >= 5 + 64 + 5


def test_async_scatter_back_overlaps(devices):
    """update() returns at dispatch with the scatter-back in flight on
    the worker thread; every table read joins first, so results are
    identical to the synchronous path."""
    m = _build(offload=True)
    m.train_iteration()
    # the finisher was submitted (the future stays until a join point)
    assert m._he_pending is not None
    # accessor is a read barrier: joins, then sees the written rows
    w1 = m.get_parameter("emb", "weight")
    assert m._he_pending is None
    # next iteration resubmits; sync() is also a read barrier
    m.train_iteration()
    assert m._he_pending is not None
    m.sync()
    assert m._he_pending is None
    w2 = m.get_parameter("emb", "weight")
    assert np.abs(w2 - w1).max() > 0  # training progressed
    # worker exceptions surface at the join point, not silently
    from concurrent.futures import Future
    f = Future()
    f.set_exception(RuntimeError("boom"))
    m._he_pending = f
    with pytest.raises(RuntimeError, match="boom"):
        m.sync()
    assert m._he_pending is None


def test_decode_params_device_caches_host_table(devices):
    """generate()'s ids are data-dependent, so decode cannot pre-gather
    rows — _decode_params moves the host table to device ONCE per table
    version instead of re-feeding the numpy table into jit per call."""
    import jax as _jax

    m = _build(offload=True)
    dp = m._decode_params()
    assert isinstance(dp["emb"]["weight"], _jax.Array)
    assert m._decode_params()["emb"]["weight"] is dp["emb"]["weight"]
    m.train_iteration()
    m.sync()
    dp3 = m._decode_params()
    # invalidated by the step's row writes, and reflects them
    assert dp3["emb"]["weight"] is not dp["emb"]["weight"]
    np.testing.assert_array_equal(np.asarray(dp3["emb"]["weight"]),
                                  m.get_parameter("emb", "weight"))
    # the training path's table stays host-resident numpy
    assert isinstance(m._params["emb"]["weight"], np.ndarray)


def test_host_table_composes_with_pipeline(devices):
    """Hetero pipeline (reference dlrm_strategy_hetero.cc: CPU tables +
    accelerator pipeline): a host-placed row-sparse embedding is lifted
    OUT of the ring as a head op — table stays host-resident numpy, its
    output feeds stage 0 like an extra input — and numerics match the
    fully device-pipelined run."""
    def run(host):
        cfg = ff.FFConfig(batch_size=16, workers_per_node=8)
        if host:
            cfg.strategies["emb"] = ff.ParallelConfig(
                DeviceType.CPU, (1, 1), (0,))
        m = ff.FFModel(cfg)
        ids = m.create_tensor((16, 4), dtype="int32", name="ids")
        t = m.embedding(ids, 1000, 8, name="emb")
        t = m.dense(t, 24, activation="relu", name="fc1")
        t = m.dense(t, 24, activation="relu", name="fc2")
        t = m.dense(t, 4, name="head")
        m.softmax(t, name="sm")
        m.set_pipeline(num_stages=2, num_microbatches=4)
        m.compile(ff.SGDOptimizer(m, lr=0.1),
                  ff.LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
                  [ff.MetricsType.ACCURACY])
        m.init_layers(seed=3)
        x = np.random.default_rng(0).integers(0, 1000, (16, 4)) \
            .astype(np.int32)
        y = (x[:, 0] % 4).astype(np.int32)[:, None]
        for _ in range(4):
            m.set_batch({ids: x}, y)
            m.train_iteration()
        m.sync()
        return m

    m_host = run(True)
    assert m_host._pipeline_plan is not None
    assert [o.name for o in m_host._pipeline_plan["head"]] == ["emb"]
    assert "emb" in m_host._host_embed  # NOT packed into the ring
    assert isinstance(m_host._params["emb"]["weight"], np.ndarray)
    m_dev = run(False)
    np.testing.assert_allclose(m_host.get_parameter("emb", "weight"),
                               m_dev.get_parameter("emb", "weight"),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(m_host.get_parameter("head", "kernel"),
                               m_dev.get_parameter("head", "kernel"),
                               rtol=2e-4, atol=2e-5)


def test_fused_optimizer_composes_with_host_table(devices):
    """fused_optimizer=True routes dense weights through the Pallas
    kernels while host tables take the plain (gather/scatter) update —
    numerics match the unfused dense run."""
    def run(host):
        m = _build(host, rows=500, fused=True)
        for _ in range(4):
            m.train_iteration()
        m.sync()
        return m

    m_h = run(True)
    assert "emb" in m_h._host_embed
    m_d = run(False)
    np.testing.assert_allclose(m_h.get_parameter("emb", "weight"),
                               m_d.get_parameter("emb", "weight"),
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(m_h.get_parameter("head", "kernel"),
                               m_d.get_parameter("head", "kernel"),
                               rtol=2e-5, atol=2e-6)


def test_sync_scatter_knob(devices, monkeypatch):
    """FF_HE_SYNC_SCATTER=1 serializes the scatter-back with the step —
    the measurement knob for an A/B of the async overlap's actual win."""
    m = _build(offload=True)
    monkeypatch.setenv("FF_HE_SYNC_SCATTER", "1")
    m.train_iteration()
    assert m._he_pending is None  # joined before update() returned
    monkeypatch.delenv("FF_HE_SYNC_SCATTER")
    m.train_iteration()
    assert m._he_pending is not None  # async again


def test_eval_uses_sparse_gather(devices):
    m = _build(offload=True)
    m.train_iteration()
    out = m.predict_batch()
    assert out.shape[0] == 16
    metrics = m.eval_batch()
    assert "loss" in metrics


def test_grad_accum_composes_with_sparse_table(devices):
    """K micro-batches per step: gathered rows cover the FULL batch's
    indices, grads average, one lazy row update — matches dense."""
    def build(offload):
        m = _build(offload, rows=300, grad_accum=2, seed=2)
        for _ in range(4):
            m.train_iteration()
        m.sync()
        return m

    m_dev = build(False)
    m_host = build(True)
    assert "emb" in m_host._host_embed
    np.testing.assert_allclose(m_dev.get_parameter("emb", "weight"),
                               m_host.get_parameter("emb", "weight"),
                               rtol=2e-5, atol=2e-6)
