"""Partition degrees → mesh axes (parallel/mesh.py, docs/lowering.md).

Contract under test: there is one walk from an op's partition degrees to
mesh-axis groups.  With an op's roles it keeps non-sample dims off the
``dcn`` axis of a hybrid mesh; without roles, and on every mesh that has
no ``dcn`` axis, it is the plain greedy walk ``Machine`` always had — kept
below as the reference.  Also pinned here: one compile per step function
through the memplane ledger, the provenance sidecar's plan stamp, and the
DCN surcharge that keeps searched strategies from putting parameter dims
on the cross-host axis.
"""

import json
import os
import re

import numpy as np
import pytest

from jax.sharding import PartitionSpec

import flexflow_tpu as ff
from flexflow_tpu.parallel import mesh
from flexflow_tpu.parallel.distributed import hybrid_machine
from flexflow_tpu.parallel.mesh import Machine
from flexflow_tpu.parallel.strategy import load_strategies_from_file
from flexflow_tpu.simulator.machine import TPUMachineModel

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# the reference: the greedy walk as Machine.axes_for_degrees had it before
# roles, and the spec Machine.spec_for_config built from it
# ---------------------------------------------------------------------------

def _greedy(axis_names, axis_sizes, degrees):
    remaining = list(zip(axis_names, axis_sizes))
    result = []
    for deg in degrees:
        group = []
        need = deg
        for i in range(len(remaining)):
            name, size = remaining[i]
            if name is None:
                continue
            if need % size == 0:
                group.append(name)
                need //= size
                remaining[i] = (None, 0)
                if need == 1:
                    break
        if need != 1:
            raise ValueError(f"degree {deg} of {list(degrees)} does not fit")
        result.append(tuple(group))
    return result


def _greedy_spec(mach, degrees, rank=None):
    degrees = list(degrees)
    if rank is not None:
        degrees = (degrees + [1] * (rank - len(degrees)))[:rank]
    groups = _greedy(mach.axis_names, mach.axis_sizes, degrees)
    entries = [g if len(g) > 1 else (g[0] if g else None) for g in groups]
    while entries and entries[-1] is None:
        entries.pop()
    return PartitionSpec(*entries)


def _layout_machine(num_devices, num_hosts=1):
    """A Machine that knows its axes and holds no devices: what
    ``spec_for_config`` reads, for layouts wider than the test mesh."""
    mach = Machine.__new__(Machine)
    mach.axis_names, mach.axis_sizes = mesh.hybrid_axis_layout(
        num_devices, num_hosts)
    return mach


# ---------------------------------------------------------------------------
# pure helpers: the layout, the walk against the reference
# ---------------------------------------------------------------------------

def test_hybrid_axis_layout_shadow():
    # 2-host v5e slice: dcn leads, ICI axes are the per-host factorization
    assert mesh.hybrid_axis_layout(16, 2) == (("dcn", "m0", "m1", "m2"),
                                              (2, 2, 2, 2))
    # single host: plain prime-factored mesh, larger factors first
    assert mesh.hybrid_axis_layout(8, 1) == (("m0", "m1", "m2"), (2, 2, 2))
    assert mesh.hybrid_axis_layout(12) == (("m0", "m1", "m2"), (3, 2, 2))
    # host count that does not divide the device count: no dcn axis
    assert mesh.hybrid_axis_layout(12, 5)[0][0] != "dcn"
    assert mesh.hybrid_axis_layout(1, 1) == (("m0",), (1,))


@pytest.mark.parametrize("degs", [
    (8, 1), (1, 8), (2, 4), (4, 2), (2, 2, 2), (4, 1, 2, 1), (1, 1), (8,),
    (2, 1, 2, 2), (1, 4, 2),
    (3,), (2, 8),  # inexpressible on 2x2x2: both sides refuse
], ids=lambda d: "x".join(map(str, d)))
def test_assign_axes_matches_machine_greedy(devices, degs):
    """On a mesh without a dcn axis (this one) the walk is the reference
    greedy, with an op's roles and without: the constraints of every
    strategy the CPU tests and the benchmark's cells compile."""
    mach = Machine(devices)
    roles = mesh.dim_roles(None, len(degs))
    try:
        want = _greedy(mach.axis_names, mach.axis_sizes, degs)
    except ValueError:
        for r in (None, roles):
            with pytest.raises(ValueError, match="not expressible"):
                mach.axes_for_degrees(degs, r)
        return
    pc = ff.ParallelConfig(dims=degs)
    for r in (None, roles):
        assert mach.axes_for_degrees(degs, r) == want, r
        assert mach.spec_for_config(pc, roles=r) == \
            _greedy_spec(mach, degs), r
        # a rank the config does not have: padded and truncated alike
        for rank in (1, len(degs) + 1):
            assert mach.spec_for_config(pc, rank=rank, roles=r) == \
                _greedy_spec(mach, degs, rank), (r, rank)
    _, spill = mesh.assign_axes(mach.axis_names, mach.axis_sizes, degs,
                                roles)
    assert spill == ()


def test_assign_axes_dcn_rules():
    """On the hybrid 16-dev/2-host layout: batch takes dcn first; a
    non-sample degree stays on ICI when it can and spills (recorded)
    only when inexpressible intra-host."""
    names, sizes = mesh.hybrid_axis_layout(16, 2)

    def walk(degs):
        return mesh.assign_axes(names, sizes, degs,
                                mesh.dim_roles(None, len(degs)))

    # pure DP: batch spans everything, never a spill
    groups, spill = walk((16, 1))
    assert groups[0][0] == "dcn" and spill == ()
    # dp2 x tp8: batch on dcn, the whole TP split stays intra-host
    groups, spill = walk((2, 8))
    assert groups == [("dcn",), ("m0", "m1", "m2")] and spill == ()
    # tp16: the parameter dim MUST take dcn to reach 16 — recorded
    groups, spill = walk((1, 16))
    assert "dcn" in groups[1]
    assert spill == ((1, 2),)
    # model parallel 4x4: splits share dcn+ici without spilling sample
    groups, spill = walk((4, 4))
    assert spill == () and groups[0][0] == "dcn"


def test_spec_string_rendering():
    assert mesh.spec_string([("m0", "m1"), (), ("m2",)]) == \
        "('m0','m1'), None, 'm2'"
    assert mesh.spec_string([(), ()]) == "replicated"
    assert mesh.spec_string([("dcn",), ("m0",)]) == "'dcn', 'm0'"


# ---------------------------------------------------------------------------
# every op of the strategy sets the repo ships and tests: the output
# constraint through Machine, with the op's roles, is the reference's spec
# ---------------------------------------------------------------------------

HYBRID = {
    "conv1": ff.ParallelConfig(dims=(2, 2, 2, 1)),
    "pool1": ff.ParallelConfig(dims=(2, 2, 1, 1)),
    "flat1": ff.ParallelConfig(dims=(2, 1)),
    "fc1": ff.ParallelConfig(dims=(2, 4)),
    "fc2": ff.ParallelConfig(dims=(2, 1)),
    "softmax1": ff.ParallelConfig(dims=(8, 1)),
}

TRANSFORMER_TP = {
    "attn_0": ff.ParallelConfig(dims=(2, 1, 4)),
    "mlp_up_0": ff.ParallelConfig(dims=(2, 4)),
    "mlp_down_0": ff.ParallelConfig(dims=(2, 1)),
    "lm_head": ff.ParallelConfig(dims=(2, 1, 4)),
    "softmax": ff.ParallelConfig(dims=(8, 1, 1)),
}


def _hybrid_graph(cfg):
    m = ff.FFModel(cfg)
    inp = m.create_tensor((16, 3, 12, 12))
    t = m.conv2d(inp, 8, 3, 3, 1, 1, 1, 1, name="conv1")
    t = m.pool2d(t, 2, 2, 2, 2, 0, 0, name="pool1")
    t = m.flat(t, name="flat1")
    t = m.dense(t, 32, name="fc1")
    t = m.dense(t, 10, name="fc2")
    m.softmax(t, name="softmax1")
    return m


def _transformer_graph(cfg):
    from flexflow_tpu.models.transformer import build_transformer

    m = ff.FFModel(cfg)
    build_transformer(m, 8, seq_length=8, num_layers=1, embed_dim=32,
                      num_heads=4, vocab_size=64)
    return m


def _alexnet_graph(cfg):
    from flexflow_tpu.models.alexnet import build_alexnet

    m = ff.FFModel(cfg)
    build_alexnet(m, 64)
    return m


def _dlrm_graph(cfg):
    from flexflow_tpu.models.dlrm import build_dlrm

    m = ff.FFModel(cfg)
    build_dlrm(m, 64, embedding_sizes=[1000] * 8)
    return m


def _nmt_graph(cfg):
    from flexflow_tpu.models.nmt import build_nmt

    m = ff.FFModel(cfg)
    build_nmt(m, 64)
    return m


def _shipped(name):
    return load_strategies_from_file(
        os.path.join(_ROOT, "strategies", name))


@pytest.mark.parametrize("graph,strategies,num_devices", [
    (_hybrid_graph, lambda: HYBRID, 8),
    (_transformer_graph, lambda: TRANSFORMER_TP, 8),
    (_alexnet_graph, lambda: _shipped("alexnet_16.pb"), 16),
    (_dlrm_graph, lambda: _shipped("dlrm_16.pb"), 16),
    (_nmt_graph, lambda: _shipped("nmt_16.pb"), 16),
], ids=["hybrid", "transformer_tp", "alexnet_16", "dlrm_16", "nmt_16"])
def test_op_constraints_match_reference(graph, strategies, num_devices):
    strategies = strategies()
    batch = 64 if num_devices == 16 else 16
    m = graph(ff.FFConfig(batch_size=batch))
    assert set(strategies) <= {op.name for op in m.ops}
    mach = _layout_machine(num_devices)
    split = 0
    for op in m.ops:
        rank = op.output.num_dims
        pc = op.legalize_pc(strategies.get(op.name) or
                            ff.ParallelConfig.data_parallel(rank,
                                                            num_devices))
        got = mach.spec_for_config(pc, rank=rank,
                                   roles=mesh.dim_roles(op, rank))
        assert got == _greedy_spec(mach, pc.dims, rank), (op.name, pc.dims)
        split += any(d > 1 for d in pc.dims[1:])
    assert split  # the set does split a dim that is not the sample's


# ---------------------------------------------------------------------------
# a dcn mesh under one process: roles keep a parameter dim on ICI, and a
# caller without roles (weights, batches) gets the plain walk
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def dcn_model(devices):
    mach = hybrid_machine(dcn_degree=2, devices=devices)
    assert mach.axis_names == ("dcn", "m0", "m1")
    cfg = ff.FFConfig(batch_size=16, compute_dtype="float32",
                      strategies={"fc1": ff.ParallelConfig(dims=(1, 4))})
    m = ff.FFModel(cfg)
    inp = m.create_tensor((16, 8), nchw=False)
    t = m.dense(inp, 32, activation=ff.ActiMode.RELU, name="fc1")
    m.softmax(m.dense(t, 4, name="fc2"), name="sm")
    m.compile(ff.SGDOptimizer(lr=0.1), "sparse_categorical_crossentropy",
              ["accuracy"], machine=mach)
    m.init_layers(seed=0)
    rng = np.random.default_rng(0)
    m.set_batch({inp: rng.standard_normal((16, 8), np.float32)},
                rng.integers(0, 4, (16, 1), dtype=np.int32))
    return m


def test_dcn_mesh_parameter_dim_stays_on_ici(dcn_model):
    m = dcn_model
    # the plain walk would hand fc1's out dim the leading axes, dcn first
    assert _greedy(m.machine.axis_names, m.machine.axis_sizes, (1, 4)) == \
        [(), ("dcn", "m0")]
    hlo = m.train_step_hlo()
    fc1 = re.findall(r"sdy\.sharding_constraint \S+ <@mesh, \[(.*?)\]> : "
                     r"tensor<16x32xf32>", hlo)
    assert fc1 and set(fc1) == {'{}, {"m0", "m1"}'}, fc1
    plan = m.machine.plan(m.ops)
    assert plan["fc1"] == {"spec": "None, ('m0','m1')", "roles": "sp"}
    # the batch is the one dim on dcn
    assert plan["fc2"]["spec"].startswith("('dcn',")
    # and the step runs with the kernel and its output on different axes
    m.train_iteration()
    m.sync()
    m.get_metrics()
    assert np.isfinite(m.last_loss)


def test_dcn_mesh_no_roles_is_the_plain_walk(dcn_model):
    mach = dcn_model.machine
    for degs in [(1, 4), (1, 2), (2, 4), (4, 2), (8,), (1, 8)]:
        assert mach.axes_for_degrees(degs) == \
            _greedy(mach.axis_names, mach.axis_sizes, degs), degs
        assert mach.spec_for_config(ff.ParallelConfig(dims=degs)) == \
            _greedy_spec(mach, degs), degs
    # fc1's kernel is mapped without roles: its out dim takes dcn first
    kernel = dcn_model._params["fc1"]["kernel"]
    assert kernel.sharding.spec == PartitionSpec(None, ("dcn", "m0"))
    assert mach.batch_sharding(8).spec == PartitionSpec(("dcn", "m0", "m1"))


# ---------------------------------------------------------------------------
# exactly one trace+compile per step function (memplane ledger)
# ---------------------------------------------------------------------------

def _tiny_dense(batch=16):
    cfg = ff.FFConfig(batch_size=batch, compute_dtype="float32")
    m = ff.FFModel(cfg)
    inp = m.create_tensor((batch, 8), nchw=False)
    t = m.dense(inp, 16, activation=ff.ActiMode.RELU, name="fc1")
    m.softmax(m.dense(t, 4, name="fc2"), name="sm")
    return m, inp


def _read_jsonl(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def test_lowered_single_compile_per_step(devices, tmp_path, monkeypatch):
    from flexflow_tpu.observability import events

    trace = str(tmp_path / "trace.jsonl")
    monkeypatch.setenv("FF_TELEMETRY", "1")
    monkeypatch.setenv("FF_TELEMETRY_FILE", trace)
    monkeypatch.setenv("FF_MEMPLANE", "1")
    events.reset_active()
    m, inp = _tiny_dense()
    m.compile(ff.SGDOptimizer(lr=0.1),
              "sparse_categorical_crossentropy", ["accuracy"])
    m.init_layers(seed=0)
    assert m._memplane is not None
    rng = np.random.default_rng(0)
    x = rng.standard_normal((16 * 3, 8), np.float32)
    y = rng.integers(0, 4, (16 * 3, 1), dtype=np.int32)
    dl = ff.DataLoader(m, {inp: x}, y)
    for _ in range(3):
        dl.next_batch(m)
        m.train_iteration()
    m.eval_batch()
    m.eval_batch()
    m.sync()
    recs = _read_jsonl(trace)
    dones = [r for r in recs if r.get("name") == "compile_done"]
    per_site = {}
    for d in dones:
        per_site[d["attrs"]["site"]] = per_site.get(d["attrs"]["site"], 0) + 1
    # ONE compile per step function across repeated calls, zero retraces
    assert per_site.get("train_step") == 1, per_site
    assert per_site.get("eval_step") == 1, per_site
    assert m._memplane.retraces == 0
    assert all(d["attrs"]["retrace"] is False for d in dones)


# ---------------------------------------------------------------------------
# introspection: Machine.plan() and the provenance sidecar stamp
# ---------------------------------------------------------------------------

def test_lowering_plan_and_sidecar_stamp(devices, tmp_path):
    pb = str(tmp_path / "hybrid.pb")
    m = _hybrid_graph(ff.FFConfig(batch_size=16, strategies=dict(HYBRID),
                                  export_strategy_file=pb))
    m.compile(ff.SGDOptimizer(lr=0.1), "sparse_categorical_crossentropy",
              ["accuracy"])
    plan = m.machine.plan(m.ops)
    # TP dense: out dim split 4 ways lands on ICI axes, roles s+p
    assert plan["fc1"]["roles"] == "sp"
    assert "m" in plan["fc1"]["spec"]
    # no dcn axis on this mesh → never a spill
    assert not any("dcn_spill" in row for row in plan.values())
    with open(pb + ".meta.json") as f:
        meta = json.load(f)
    assert "lowered" not in meta
    assert meta["lowering"] == plan
    # per-op attribution rows carry the resolved spec for --diff
    assert "spec" in next(iter(meta["ops"].values()))


# ---------------------------------------------------------------------------
# DCN placement: machine-model surcharge and search pressure
# ---------------------------------------------------------------------------

def test_machine_dcn_spill_detection():
    mm = TPUMachineModel(num_devices=16)  # 2 hosts at 8 chips/host
    assert mm.num_hosts == 2
    # pure DP / dp2xtp8 / mp4x4: no non-sample dim crosses hosts
    assert mm.dcn_spill((16, 1)) == ()
    assert mm.dcn_spill((2, 8)) == ()
    assert mm.dcn_spill((4, 4)) == ()
    # tp16 forces the parameter dim across hosts
    assert mm.dcn_spill((1, 16)) == ((1, 2),)
    assert mm.dcn_spill_time((1, 16), 1e6) > 0
    assert mm.dcn_spill_time((2, 8), 1e6) == 0.0
    # single host: nothing to spill onto
    assert TPUMachineModel(num_devices=8).dcn_spill((1, 8)) == ()


def test_cost_model_charges_dcn_spill(devices):
    from flexflow_tpu.simulator.cost_model import CostModel

    m, _ = _tiny_dense(batch=64)
    op = next(o for o in m.ops if o.name == "fc1")
    mm = TPUMachineModel(num_devices=16)
    cm = CostModel(mm, cache_path=None)
    spilled = ff.ParallelConfig(dims=(1, 16))
    clean = ff.ParallelConfig(dims=(2, 8))
    assert cm._dcn_penalty(op, spilled) > 0
    assert cm._dcn_penalty(op, clean) == 0.0
    # the penalty lands in op_time (and sticks through the fast memo)
    t = cm.op_time(op, spilled, "forward")
    assert t >= cm._dcn_penalty(op, spilled)
    assert cm.op_time(op, spilled, "forward") == t


def test_search_never_spills_parameter_dims_to_dcn(devices):
    """Seeded MCMC over a 2-host simulated machine: the surcharge must
    keep every chosen config off the dcn axis for non-sample dims —
    gradient all-reduce stays the only DCN-crossing collective."""
    from flexflow_tpu.simulator.search import mcmc_search

    cfg = ff.FFConfig(batch_size=64, workers_per_node=16)
    m = ff.FFModel(cfg)
    inp = m.create_tensor((64, 64), nchw=False)
    t = m.dense(inp, 128, activation=ff.ActiMode.RELU, name="d1")
    t = m.dense(t, 64, activation=ff.ActiMode.RELU, name="d2")
    t = m.dense(t, 16, name="d3")
    m.softmax(t, name="sm")
    mm = TPUMachineModel(num_devices=16)
    res = mcmc_search(m, budget=300, seed=0, machine_model=mm,
                      verbose=False)
    assert res  # non-empty strategy map
    for name, pc in res.items():
        assert mm.dcn_spill(pc.dims) == (), (name, pc.dims)


# ---------------------------------------------------------------------------
# the image stem computed space-to-depth (ops/conv2d.py): the step's
# program, the stored kernel, checkpoints, and a stem split by height or
# width across devices
# ---------------------------------------------------------------------------

def _small_alexnet(devices=1, strategies=None, direct=False, batch=8,
                   image=67):
    """AlexNet at a small image in f32, one batch staged.  ``direct``
    makes every convolution the plain strided one: the reference."""
    from flexflow_tpu.models.alexnet import build_alexnet

    cfg = ff.FFConfig(batch_size=batch, workers_per_node=devices,
                      compute_dtype="float32",
                      strategies=dict(strategies or {}))
    m = ff.FFModel(cfg)
    inp, _ = build_alexnet(m, batch, height=image, width=image)
    if direct:
        for op in m.ops:
            if op._type == "Conv2D":
                op.impl_used = ("direct", "the test's reference")
    m.compile(ff.SGDOptimizer(lr=0.01), "sparse_categorical_crossentropy",
              ["accuracy"])
    m.init_layers(seed=5)
    rng = np.random.default_rng(5)
    m.set_batch({inp: rng.standard_normal((batch, image, image, 3),
                                          dtype=np.float32)},
                rng.integers(0, 10, (batch, 1), dtype=np.int32))
    return m


def _losses(m, steps=5):
    out = []
    for _ in range(steps):
        m.reset_metrics()
        m.train_iteration()
        m.sync()
        m.get_metrics()
        out.append(m.last_loss)
    return out


def _conv_windows(hlo):
    """(kernel type, ...) of every convolution in a StableHLO text:
    ``11x11x3x64xf32`` for an 11x11 window over 3 features."""
    return re.findall(r"stablehlo\.convolution\(.*?: \(tensor<[^>]*>, "
                      r"tensor<([^>]*)>\)", hlo)


def test_alexnet_stem_step_program(devices):
    m = _small_alexnet()
    assert [op.name for op in m.ops if op._type == "Conv2D"
            and op.impl_used[0] == "space_to_depth"] == ["conv1"]
    windows = _conv_windows(m.train_step_hlo())
    assert not [w for w in windows if w.startswith("11x11x")], windows
    # forward and kernel gradient of the 3x3 over 48 (the data has no
    # gradient), and the one-hot rearrangement of the image before them
    assert windows.count("3x3x48x64xf32") == 1, windows
    assert windows.count("4x4x3x48xf32") == 1, windows
    # conv2..conv5 as they always were
    assert "5x5x64x192xf32" in windows and "3x3x192x384xf32" in windows
    assert m.get_parameter("conv1", "kernel").shape == (11, 11, 3, 64)
    assert m.ops[0].flops_per_sample() == 2.0 * 16 * 16 * 64 * 11 * 11 * 3
    # the reference's program does hold the 11x11
    ref = _small_alexnet(direct=True)
    assert "11x11x3x64xf32" in _conv_windows(ref.train_step_hlo())


def test_alexnet_stem_trains_as_the_direct_form(devices, tmp_path):
    ref = _small_alexnet(direct=True)
    # a checkpoint written by the direct form: the layout every earlier
    # checkpoint has
    ckpt = str(tmp_path / "direct.npz")
    ref.save(ckpt)
    m = _small_alexnet()
    m.load(ckpt)
    for name in ("conv1", "conv2", "fc3"):
        np.testing.assert_array_equal(m.get_parameter(name, "kernel"),
                                      ref.get_parameter(name, "kernel"))
    k_first = m.get_parameter("conv1", "kernel")
    want, got = _losses(ref), _losses(m)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    assert got[-1] < got[0]
    k_ref, k = (x.get_parameter("conv1", "kernel") for x in (ref, m))
    assert np.abs(k_ref).max() > 0
    np.testing.assert_allclose(k, k_ref, rtol=1e-4, atol=1e-6)
    assert not np.array_equal(k, k_first)  # and the steps moved it


@pytest.fixture(scope="module")
def stem_one_device_losses(devices):
    return _losses(_small_alexnet())


@pytest.mark.parametrize("dims", [(2, 2, 1, 1), (2, 1, 2, 1)],
                         ids=["sample-x-height", "sample-x-width"])
def test_alexnet_stem_split_across_devices(stem_one_device_losses, dims):
    """conv1's output constrained sample x height or sample x width on
    four devices: GSPMD carries the split through the rearrangement and
    the loss is the one-device loss."""
    want = stem_one_device_losses
    m = _small_alexnet(devices=4,
                       strategies={"conv1": ff.ParallelConfig(dims=dims)})
    assert m.machine.num_devices == 4
    assert tuple(m.ops[0].pc.dims) == dims
    assert m.ops[0].impl_used[0] == "space_to_depth"
    np.testing.assert_allclose(_losses(m), want, rtol=1e-5)
