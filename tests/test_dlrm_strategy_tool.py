"""DLRM strategy generators (reference: src/runtime/dlrm_strategy*.cc).

The generated files must be wire-compatible, load under
reference-order semantics, and actually drive a DLRM model's compile.
"""

import os
import shutil
import subprocess

import numpy as np
import pytest

import flexflow_tpu as ff
from flexflow_tpu.config import DeviceType
from flexflow_tpu.models.dlrm import build_dlrm, synthetic_batch
from flexflow_tpu.parallel.strategy import load_strategies_from_file
from flexflow_tpu.tools import dlrm_strategy


def test_generate_matches_reference_layout(tmp_path):
    out = str(tmp_path / "s.pb")
    dlrm_strategy.main(["--gpu", "4", "--node", "2", "-o", out])
    loaded = load_strategies_from_file(out, reference_order=True)
    assert len(loaded) == 24 + 3
    # Reference: embedding i on device i % total, dims (1,1).
    assert loaded["embedding5"].device_ids == (5,)
    assert loaded["embedding5"].dims == (1, 1)
    # concat split across nodes (sample dim first after reversal).
    assert loaded["concat"].dims == (2, 1)
    assert loaded["concat"].device_ids == (0, 4)
    assert loaded["linear"].dims == (8, 1)
    assert loaded["mse_loss"].memory_types == ("hbm",)


def test_generate_hetero_places_tables_on_host(tmp_path):
    out = str(tmp_path / "h.pb")
    dlrm_strategy.main(["--hetero", "--gpu", "2", "-o", out])
    loaded = load_strategies_from_file(out, reference_order=True)
    assert loaded["embedding0"].device_type == DeviceType.CPU
    assert loaded["embedding0"].memory_types == ("host", "host", "host")
    assert loaded["linear"].dims == (2, 1)


@pytest.mark.skipif(
    shutil.which("protoc") is None
    or not os.path.exists("/root/reference/src/runtime/strategy.proto"),
    reason="needs protoc and the reference checkout's strategy.proto, "
           "which is outside this repo")
def test_generated_file_decodes_with_reference_schema(tmp_path):
    out = str(tmp_path / "s.pb")
    dlrm_strategy.main(["--gpu", "1", "--node", "1", "--emb", "4", "-o", out])
    with open(out, "rb") as f:
        dec = subprocess.run(
            ["protoc", "--proto_path=/root/reference/src/runtime",
             "--decode=FFProtoBuf.Strategy", "strategy.proto"],
            stdin=f, capture_output=True, check=True)
    text = dec.stdout.decode()
    assert 'name: "embedding0"' in text
    assert "memory_types: FBM" in text


def test_dlrm_trains_with_generated_strategy(devices, tmp_path):
    out = str(tmp_path / "s.pb")
    # 8 virtual chips on one node: MLPs DP over 8, embeddings round-robin.
    dlrm_strategy.main(["--gpu", "8", "--node", "1", "--emb", "4", "-o", out])
    sizes = [64] * 4
    cfg = ff.FFConfig(batch_size=16, compute_dtype="float32",
                      import_strategy_file=out,
                      import_strategy_reference_order=True)
    m = ff.FFModel(cfg)
    sparse, dense, p = build_dlrm(m, 16, embedding_sizes=sizes,
                                  embedding_bag_size=2,
                                  sparse_feature_size=8,
                                  mlp_bot=[8, 16, 8],
                                  mlp_top=[8 * 5, 16, 1])
    m.compile(ff.SGDOptimizer(lr=0.05), ff.LossType.MEAN_SQUARED_ERROR_AVG_REDUCE,
              [ff.MetricsType.MEAN_SQUARED_ERROR])
    emb_op = next(op for op in m.ops if op.name == "embedding1")
    assert emb_op.pc.dims == (1, 1)
    m.init_layers()
    xs, xd, y = synthetic_batch(16, sizes, 2, 8)
    m.set_batch({t: a for t, a in zip(sparse + [dense], xs + [xd])}, y)
    for _ in range(3):
        m.train_iteration()
    m.sync()


def test_hetero_strategy_file_drives_row_sparse_runtime(devices, tmp_path):
    """End-to-end parity story: a reference-wire-format HETERO strategy
    file (dlrm_strategy_hetero.cc's output shape) imported into compile
    routes the tables onto the row-sparse host-resident path — the
    file a reference user already has drives the TPU-native feature."""
    out = str(tmp_path / "h.pb")
    dlrm_strategy.main(["--hetero", "--gpu", "8", "--emb", "4", "-o", out])
    sizes = [64] * 4
    cfg = ff.FFConfig(batch_size=16, compute_dtype="float32",
                      import_strategy_file=out,
                      import_strategy_reference_order=True)
    m = ff.FFModel(cfg)
    sparse, dense, p = build_dlrm(m, 16, embedding_sizes=sizes,
                                  embedding_bag_size=2,
                                  sparse_feature_size=8,
                                  mlp_bot=[8, 16, 8],
                                  mlp_top=[8 * 5, 16, 1])
    m.compile(ff.SGDOptimizer(lr=0.05),
              ff.LossType.MEAN_SQUARED_ERROR_AVG_REDUCE,
              [ff.MetricsType.MEAN_SQUARED_ERROR])
    m.init_layers()
    # all four tables took the row-sparse host path (numpy tables)
    assert len(m._host_embed) == 4, m._host_embed
    assert isinstance(m._params["embedding0"]["weight"], np.ndarray)
    xs, xd, y = synthetic_batch(16, sizes, 2, 8)
    m.set_batch({t: a for t, a in zip(sparse + [dense], xs + [xd])}, y)
    for _ in range(3):
        m.train_iteration()
    m.sync()
