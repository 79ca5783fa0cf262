"""Simulator + MCMC search tests.

Golden-property tests (SURVEY.md §4 implication: "golden-file tests for
the strategy search"): the simulator must rank obviously-better strategies
ahead of worse ones, and the search must return legal strategies that
simulate no slower than pure data parallelism.
"""

import numpy as np
import pytest

import flexflow_tpu as ff
from flexflow_tpu.config import ParallelConfig
from flexflow_tpu.simulator.cost_model import CostModel
from flexflow_tpu.simulator.machine import TPUMachineModel
from flexflow_tpu.simulator.search import mcmc_search, random_parallel_config
from flexflow_tpu.simulator.simulator import Simulator


def tiny_model(batch=64):
    m = ff.FFModel(ff.FFConfig(batch_size=batch))
    inp = m.create_tensor((batch, 3, 32, 32))
    t = m.conv2d(inp, 16, 3, 3, 1, 1, 1, 1, activation="relu", name="conv1")
    t = m.pool2d(t, 2, 2, 2, 2, 0, 0, name="pool1")
    t = m.flat(t, name="flat1")
    t = m.dense(t, 256, activation="relu", name="fc1")
    t = m.dense(t, 16, name="fc2")
    t = m.softmax(t, name="softmax1")
    m.compile(ff.SGDOptimizer(lr=0.1), "sparse_categorical_crossentropy", ["accuracy"])
    return m


def compute_heavy_model(batch=256):
    """Enough conv FLOPs per sample that DP beats single-device despite
    the gradient allreduce (the crossover the simulator must capture)."""
    m = ff.FFModel(ff.FFConfig(batch_size=batch))
    inp = m.create_tensor((batch, 3, 64, 64))
    t = m.conv2d(inp, 32, 3, 3, 1, 1, 1, 1, activation="relu", name="conv1")
    t = m.conv2d(t, 64, 3, 3, 1, 1, 1, 1, activation="relu", name="conv2")
    t = m.pool2d(t, 2, 2, 2, 2, 0, 0, name="pool1")
    t = m.conv2d(t, 64, 3, 3, 1, 1, 1, 1, activation="relu", name="conv3")
    t = m.pool2d(t, 4, 4, 4, 4, 0, 0, name="pool2")
    t = m.flat(t, name="flat1")
    t = m.dense(t, 64, activation="relu", name="fc1")
    t = m.dense(t, 16, name="fc2")
    t = m.softmax(t, name="softmax1")
    m.compile(ff.SGDOptimizer(lr=0.1), "sparse_categorical_crossentropy", ["accuracy"])
    return m


def test_machine_model_torus():
    mm = TPUMachineModel(num_devices=16)
    assert mm.torus == (4, 4)
    assert mm.hops(0, 0) == 0
    assert mm.hops(0, 1) == 1
    # wraparound: chip 0 (0,0) to chip 3 (3,0) is 1 hop on a 4-ring
    assert mm.hops(0, 3) == 1
    assert mm.transfer_time(0, 0, 1e6) == 0.0
    assert mm.transfer_time(0, 1, 1e6) > 0.0
    # allreduce cost grows with bytes, sublinearly with group size
    t2 = mm.allreduce_time([0, 1], 1e6)
    t4 = mm.allreduce_time([0, 1, 2, 3], 1e6)
    assert t4 > t2
    assert t4 < 2 * t2


def test_simulator_prefers_parallelism(devices):
    m = compute_heavy_model()
    mm = TPUMachineModel(num_devices=8)
    sim = Simulator(mm, CostModel(mm, measure=False))
    single = {op.name: ParallelConfig(dims=(1,) * op.output.num_dims, device_ids=(0,))
              for op in m.ops}
    dp8 = {op.name: ParallelConfig.data_parallel(op.output.num_dims, 8)
           for op in m.ops}
    t1 = sim.simulate_runtime(m, single)
    t8 = sim.simulate_runtime(m, dp8)
    assert t8 < t1, f"DP8 ({t8}) should beat single-device ({t1})"


def test_simulator_charges_comm(devices):
    m = tiny_model()
    mm = TPUMachineModel(num_devices=8)
    sim = Simulator(mm, CostModel(mm, measure=False))
    dp = {op.name: ParallelConfig.data_parallel(op.output.num_dims, 8)
          for op in m.ops}
    # same strategy but fc1 split over channels: adds resharding comm
    mixed = dict(dp)
    mixed["fc1"] = ParallelConfig(dims=(1, 8), device_ids=tuple(range(8)))
    t_dp = sim.simulate_runtime(m, dp)
    t_mixed = sim.simulate_runtime(m, mixed)
    assert t_mixed != t_dp  # the comm model must see the difference


def test_random_config_is_legal(devices):
    import random

    m = tiny_model()
    rng = random.Random(0)
    for op in m.ops:
        for _ in range(20):
            pc = random_parallel_config(op, 8, rng)
            assert pc.num_parts() <= 8
            for i, d in enumerate(pc.dims):
                assert op.output.dims[i] % d == 0


def test_mcmc_search_improves_or_matches_dp(devices):
    m = tiny_model()
    best = mcmc_search(m, budget=60, alpha=0.05, seed=3, verbose=False)
    assert set(best) == {op.name for op in m.ops}
    mm = TPUMachineModel(num_devices=8)
    sim = Simulator(mm, CostModel(mm, measure=False))
    dp = {op.name: ParallelConfig.data_parallel(op.output.num_dims, 8)
          for op in m.ops}
    assert sim.simulate_runtime(m, best) <= sim.simulate_runtime(m, dp) * 1.0001


def test_search_result_trains(devices):
    """The searched strategy must actually run: compile a model with it."""
    m = tiny_model(batch=32)
    best = mcmc_search(m, budget=30, alpha=0.05, seed=1, verbose=False)
    cfg = ff.FFConfig(batch_size=32, strategies=best)
    m2 = ff.FFModel(cfg)
    inp = m2.create_tensor((32, 3, 32, 32))
    t = m2.conv2d(inp, 16, 3, 3, 1, 1, 1, 1, activation="relu", name="conv1")
    t = m2.pool2d(t, 2, 2, 2, 2, 0, 0, name="pool1")
    t = m2.flat(t, name="flat1")
    t = m2.dense(t, 256, activation="relu", name="fc1")
    t = m2.dense(t, 16, name="fc2")
    t = m2.softmax(t, name="softmax1")
    m2.compile(ff.SGDOptimizer(lr=0.1), "sparse_categorical_crossentropy", ["accuracy"])
    m2.init_layers()
    dl = ff.DataLoader.synthetic(m2, inp, num_samples=32, num_classes=16)
    dl.next_batch(m2)
    m2.train_iteration()
    m2.sync()


def test_compile_runs_search_with_budget(devices, tmp_path):
    path = str(tmp_path / "searched.pb")
    cfg = ff.FFConfig(batch_size=64, search_budget=20,
                      export_strategy_file=path)
    m = ff.FFModel(cfg)
    inp = m.create_tensor((64, 3, 16, 16))
    t = m.conv2d(inp, 8, 3, 3, 1, 1, 1, 1, name="c1")
    t = m.flat(t, name="f1")
    t = m.dense(t, 32, name="d1")
    t = m.softmax(t, name="s1")
    m.compile(ff.SGDOptimizer(lr=0.1), "sparse_categorical_crossentropy", ["accuracy"])
    loaded = ff.load_strategies_from_file(path)
    assert set(loaded) == {"c1", "f1", "d1", "s1"}


def test_host_embedding_cost_scales_with_batch_not_table(devices):
    """Host-placed (row-sparse) embedding pricing mirrors the runtime:
    per-step cost follows the BATCH's rows, independent of table size
    (reference: embedding.cc CPU tasks touch only the batch's rows)."""
    from flexflow_tpu.config import DeviceType, ParallelConfig
    from flexflow_tpu.simulator.cost_model import CostModel
    from flexflow_tpu.simulator.machine import TPUMachineModel

    def emb_op(batch, rows):
        m = ff.FFModel(ff.FFConfig(batch_size=batch))
        ids = m.create_tensor((batch, 2), dtype="int32", name="ids")
        m.embedding(ids, rows, 16, name="emb")
        return m.ops[0]

    mm = TPUMachineModel(num_devices=8)
    cost = CostModel(mm, measure=False)
    cpu_pc = ParallelConfig(DeviceType.CPU, (1, 1), (0,),
                            ("host", "host", "host"))
    t_small = cost.op_time(emb_op(64, 10_000), cpu_pc, "forward")
    t_large = cost.op_time(emb_op(64, 10_000_000), cpu_pc, "forward")
    assert t_small == t_large  # table size is NOT in the cost
    t_2x = cost.op_time(emb_op(128, 10_000), cpu_pc, "forward")
    assert t_2x > t_small  # batch rows ARE
    # backward adds the PCIe return + host scatter
    t_bwd = cost.op_time(emb_op(64, 10_000), cpu_pc, "backward")
    assert t_bwd > t_small


def test_host_embedding_prices_transfer_latency(devices):
    """A fitted per-transfer host<->device latency must raise the
    host-embedding cost — without it the search over-recommends host
    placement on latency-bound deployments."""
    import flexflow_tpu as ff
    from flexflow_tpu.simulator.cost_model import CostModel
    from flexflow_tpu.simulator.machine import TPUMachineModel

    cfg = ff.FFConfig(batch_size=64)
    m = ff.FFModel(cfg)
    ids = m.create_tensor((64, 4), dtype="int32", name="ids")
    m.embedding(ids, 10000, 16, name="emb")
    op = m.ops[0]
    pc = ff.ParallelConfig.host_rowsparse()
    base = CostModel(TPUMachineModel(num_devices=8),
                     measure=False).op_time(op, pc, "forward")
    slow = CostModel(TPUMachineModel(num_devices=8, host_xfer_latency=30e-3),
                     measure=False).op_time(op, pc, "forward")
    assert slow > base + 25e-3
