"""The benchmark's harness (benchmark/run.py) at a tiny size on the
virtual CPU devices, through its functions: the command itself refuses a
CPU.  The cells run here are added the way a later PR adds one: new
files and new entries, no edit to a file that is there.  Nothing this
file measures is a speed."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import run

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BIG_SEED = 2 ** 31 + 11   # the driver's seeds pass 32 signed bits


@pytest.fixture(scope="module")
def root(grown):
    """A copy of the benchmark with tiny cells added: files and entries
    only (`conftest.py`; `test_benchmark_cells.py` holds that no file
    was edited)."""
    return grown.top


def _cell(root, name):
    """The cell, with the peaks of whatever the tests run on put in:
    the real table has the TPU alone, and must."""
    import jax

    cell = run.load_cell(root, name)
    assert jax.devices()[0].device_kind not in cell["peaks"]
    cell["peaks"] = {jax.devices()[0].device_kind: {
        "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}
    return cell


def test_added_cell_is_found_by_name(root):
    cell = run.load_cell(root, "alexnet-tiny.resident")
    assert cell["config"]["builder_kwargs"]["height"] == 67     # the config
    assert cell["traffic"]["batch_per_chip"] == 8               # the traffic
    assert cell["layer_metrics"]["sync_ms_per_block"]["span"] == "bench.sync"
    # metrics with no list of cells apply to the new cell as well
    assert "host_dispatch_ms_per_step" in cell["layer_metrics"]
    assert "convolution_ms_per_step" not in cell["layer_metrics"]
    assert {m["name"] for m in cell["end_to_end"]} == {
        "samples_per_s_per_chip", "mfu", "step_ms_p90", "setup_s"}
    ref = run.load_reference(cell["home"], cell["config"]["reference"])
    assert ref.CHUNK == 256
    # the searched-against-dp metrics come back the same way, as files
    four = run.load_cell(root, "alexnet-tiny.4dev")["layer_metrics"]
    assert four["search_s"]["variant"] == "searched"
    assert four["sim_predicted_searched_over_dp"]["over"] == \
        "sim_step_s.searched"
    assert "search_s" not in cell["layer_metrics"]
    with pytest.raises(SystemExit):
        run.load_cell(root, "no-such-cell")


def _check_result(res, names):
    assert set(res) == {"correct", "attempted", "failed", "metrics",
                        "device"}
    assert res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == set(names)
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert res["device"]["platform"] == "cpu"
    assert set(res["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}


@pytest.mark.parametrize("name", ["alexnet-tiny.resident",
                                  "gpt2-tiny.resident"])
def test_tiny_cell_on_one_device(root, name):
    lines = []
    res = run.run_cell(_cell(root, name), BIG_SEED, 1.0, False,
                       say=lines.append)
    _check_result(res, ["samples_per_s_per_chip", "mfu", "step_ms_p90",
                        "setup_s"])
    assert any("first loss" in ln and "reference" in ln for ln in lines)
    assert res["metrics"]["mfu"]["value"] < 1.0
    # the log says where the longest blocks' time went
    slow = json.loads(next(ln for ln in lines if "slowest blocks" in ln)
                      .split("slowest blocks ")[1])
    assert 1 <= len(slow) <= 3 and slow[0]["ms"] >= slow[-1]["ms"]
    assert {"block", "ms", "train_iteration_ms", "sync_ms",
            "read_loss_ms"} <= set(slow[0])
    assert slow[0]["train_iteration_ms"] + slow[0]["sync_ms"] \
        + slow[0]["read_loss_ms"] <= slow[0]["ms"] + 0.02


def test_tiny_cell_on_four_devices(root):
    # the harness names the ratio after the traffic file's two variants
    lines = []
    res = run.run_cell(_cell(root, "alexnet-tiny.4dev"), 7, 1.0, False,
                       say=lines.append)
    _check_result(res, ["samples_per_s_per_chip", "mfu", "step_ms_p90",
                        "setup_s", "searched_over_dp"])
    assert any(ln.startswith("searched: ops not plainly data parallel")
               for ln in lines)
    assert any("first warm-up block loss dp" in ln for ln in lines)
    # both strategies got the same number of blocks
    assert res["attempted"] % 2 == 0


def test_tiny_data_parallel_cell_on_four_devices(root):
    """One variant across four devices, as alexnet-4chip-dp is: the
    shard check runs, and there is no ratio and no simulation."""
    lines = []
    res = run.run_cell(_cell(root, "alexnet-tiny.4dev-dp"), BIG_SEED, 1.0,
                       False, say=lines.append)
    _check_result(res, ["samples_per_s_per_chip", "mfu", "step_ms_p90",
                        "setup_s"])
    assert "dp: ops not plainly data parallel: {}" in lines
    assert not any("FAULT" in ln or "CHECK FAILED" in ln for ln in lines)


def test_same_seed_same_batch(root):
    import jax
    import numpy as np

    ref = run.load_reference(os.path.join(root, "benchmark"), "gpt2-medium")
    kw = dict(seq_length=16, vocab_size=50)
    a = ref.make_batch(jax.random.key(BIG_SEED % run.SEED_MODULUS), 2, **kw)
    b = ref.make_batch(jax.random.key(BIG_SEED % run.SEED_MODULUS), 2, **kw)
    c = ref.make_batch(jax.random.key(5), 2, **kw)
    assert np.array_equal(a[0][0], b[0][0]) and np.array_equal(a[1], b[1])
    assert not np.array_equal(a[0][0], c[0][0])
    assert np.array_equal(a[1][:, :-1], a[0][0][:, 1:])   # next-token labels


def test_traced_run_without_a_device_trace_is_refused(root):
    """On the CPU the profile has no device plane: the traced run goes
    all the way through the profiler and the readers, and then refuses
    to report, as a run in which nothing ran on the device must."""
    with pytest.raises(SystemExit, match="no device operation"):
        run.run_cell(_cell(root, "alexnet-tiny.resident"), 3, 1.0, True,
                     say=lambda _: None)


def test_unknown_device_kind_is_refused(root):
    cell = run.load_cell(root, "alexnet-tiny.resident")
    with pytest.raises(SystemExit, match="not in peaks.json"):
        run.run_cell(cell, 3, 1.0, False, say=lambda _: None)


def test_too_few_chips_is_refused(root):
    cell = _cell(root, "alexnet-tiny.resident")
    cell["chips"] = 64
    with pytest.raises(SystemExit, match="needs 64 chip"):
        run.run_cell(cell, 3, 1.0, False, say=lambda _: None)


def test_command_refuses_a_cpu_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", "alexnet-train-resident", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr and "platform=cpu" in p.stderr
