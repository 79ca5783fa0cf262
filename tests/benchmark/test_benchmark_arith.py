"""The benchmark's arithmetic: blocks, percentiles, FLOP formulas, and
`BENCHMARK.json` against its contract.  CPU only, nothing timed."""

import json
import os
import re

import pytest

from benchmark import flops
from benchmark.run import (block_stats, formula, percentile,
                           steps_per_block)

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.mark.parametrize("step_s,min_ms,want", [
    (0.0098, 100, 11), (0.010, 100, 10), (0.0775, 100, 2), (0.1066, 100, 1),
    (0.5, 100, 1), (0.05, 100, 2), (0.0499, 100, 3),
    # the three cells' synced single steps at block_min_ms 300, and how
    # far each may move before k does
    (0.082, 300, 4), (0.076, 300, 4), (0.099, 300, 4),
    (0.113, 300, 3), (0.101, 300, 3), (0.149, 300, 3), (0.088, 300, 4),
    # since PR 34: AlexNet on one chip at 270 (k = 4 from 67.5 to 90 ms)
    # and DeepSeek-V2 at 1600 (k = 6 from 266.7 to 320), each in the middle
    (0.0784, 270, 4), (0.068, 270, 4), (0.0899, 270, 4), (0.0901, 270, 3),
    (0.0674, 270, 5), (0.298, 1600, 6), (0.267, 1600, 6), (0.3199, 1600, 6),
    (0.3201, 1600, 5), (0.2666, 1600, 7)])
def test_steps_per_block(step_s, min_ms, want):
    assert steps_per_block(step_s, min_ms) == want


def test_percentile():
    v = list(range(1, 102))            # 1..101: the q-th percentile is q+1
    assert percentile(v, 0) == 1
    assert percentile(v, 50) == 51
    assert percentile(v, 90) == 91
    assert percentile(v, 100) == 101
    assert percentile([1.0, 2.0], 90) == pytest.approx(1.9)
    assert percentile([7.0], 90) == 7.0
    assert percentile([3, 1, 2], 50) == 2      # sorts
    with pytest.raises(ValueError):
        percentile([], 50)


def test_block_stats_is_all_work_over_all_time():
    # 99 blocks of 0.2 s and one stall of 2 s, k = 2, 256 samples, 4 chips
    blocks = [(0.2, True)] * 99 + [(2.0, True)]
    s = block_stats(blocks, k=2, global_batch=256, chips=4)
    # the rate is every sample over every second: the stall shows in it
    assert s["samples_per_s_per_chip"] == pytest.approx(
        256 * 2 * 100 / (99 * 0.2 + 2.0) / 4)
    assert s["samples_per_s_per_chip"] < 0.92 * 640.0
    # the medians beside it, and alone the tail, do not move for one block
    assert s["block_median_samples_per_s_per_chip"] == pytest.approx(640.0)
    assert s["step_ms_p50"] == pytest.approx(100.0)
    assert s["step_ms_p90"] == pytest.approx(100.0)
    assert s["blocks"] == 100
    # eleven stalls in a hundred blocks do reach the 90th percentile
    s = block_stats([(0.2, True)] * 89 + [(2.0, True)] * 11, k=2,
                    global_batch=256, chips=4)
    assert s["block_median_samples_per_s_per_chip"] == pytest.approx(640.0)
    assert s["step_ms_p90"] > 100.0


def test_block_stats_counts_a_failed_block_s_time_and_not_its_samples():
    s = block_stats([(0.2, True), (0.2, False), (0.2, True)], k=1,
                    global_batch=100, chips=1)
    assert s["samples_per_s_per_chip"] == pytest.approx(200 / 0.6)
    assert s["blocks"] == 2
    assert s["step_ms_p90"] == pytest.approx(200.0)


def test_run_block_times_from_the_end_of_the_block_before():
    """The drain of the metrics and whatever the host did since the
    block before lie inside the block's clock."""
    import time

    from benchmark.run import Spans, Variant, run_block

    class Model:
        last_loss = 1.0
        calls = []

        def train_iteration(self):
            self.calls.append("step")

        def sync(self):
            self.calls.append("sync")

        def get_metrics(self):
            time.sleep(0.02)
            self.calls.append("drain")

    v = Variant("main", Model())
    spans = Spans()
    t0 = time.perf_counter()
    t1 = run_block(v, 3, spans, print)
    time.sleep(0.03)                      # the host, between two blocks
    t2 = run_block(v, 3, spans, print, since=t1)
    assert Model.calls == (["step"] * 3 + ["sync", "drain"]) * 2
    (_, dt1, loss, ok), (_, dt2, _, _) = v.blocks
    assert ok and loss == 1.0
    assert 0.02 <= dt1 <= t1 - t0
    assert dt2 == pytest.approx(t2 - t1) and dt2 >= 0.05
    assert spans.seconds("bench.read_loss", "main")[0] >= 0.02


def test_bytes_held_and_the_overlap_check():
    from benchmark.run import Refused, bytes_held, read_memory

    class Dev:
        id = 0

        def __init__(self, stats):
            self.stats = stats

        def memory_stats(self):
            return self.stats

    ok = {"bytes_limit": 1000 * 2 ** 20, "bytes_in_use": 100 * 2 ** 20,
          "bytes_reserved": 300 * 2 ** 20,
          "largest_free_block_bytes": 600 * 2 ** 20,
          "peak_bytes_in_use": 150 * 2 ** 20,
          "peak_bytes_reserved": 300 * 2 ** 20}
    said = []
    rows = read_memory([Dev(ok), Dev(None)], said.append)
    assert bytes_held(rows[0]) == 400 * 2 ** 20 and bytes_held(rows[1]) == 0
    assert "bytes_reserved=%d" % (300 * 2 ** 20) in said[0]
    assert "largest_free_block_bytes=" in said[0] and "limit=0" in said[0]
    assert "no allocator statistics" in said[1]
    # a reservation inside the heap: the free block passes what the sum
    # leaves, and the run is refused
    inside = dict(ok, largest_free_block_bytes=900 * 2 ** 20)
    with pytest.raises(Refused, match="overlap"):
        read_memory([Dev(inside)], said.append)


def test_alexnet_flops_by_hand():
    # 229 -> conv 11/4/2 -> 56 -> pool -> 27 -> conv 5/1/2 -> 27 -> pool
    # -> 13 -> three 3x3 -> 13 -> pool -> 6; 6*6*256 = 9216
    by_hand = {
        "conv1": 2 * 56 * 56 * 64 * 11 * 11 * 3,
        "conv2": 2 * 27 * 27 * 192 * 5 * 5 * 64,
        "conv3": 2 * 13 * 13 * 384 * 3 * 3 * 192,
        "conv4": 2 * 13 * 13 * 256 * 3 * 3 * 384,
        "conv5": 2 * 13 * 13 * 256 * 3 * 3 * 256,
        "fc1": 2 * 9216 * 4096, "fc2": 2 * 4096 * 4096, "fc3": 2 * 4096 * 10}
    rows = {r[0]: r for r in flops.alexnet_layers()}
    assert {k: r[2] for k, r in rows.items()} == by_hand
    assert sum(by_hand.values()) == 1_425_424_384          # 1.43 GFLOP
    assert flops.alexnet_forward() == 1_425_424_384
    assert flops.alexnet_train() == 3 * 1_425_424_384      # 4.28 GFLOP
    assert rows["fc1"][3] == 9216
    params = sum(r[4] for r in rows.values()) \
        + 64 + 192 + 384 + 256 + 256 + 4096 + 4096 + 10
    assert params == 57_044_810
    need, nbytes = flops.alexnet_matmuls(batch=2)
    assert need == 2 * (3 * 1_425_424_384 - by_hand["conv1"])
    assert nbytes > 0


def test_alexnet_flops_beside_the_program_s_own(capsys):
    """Printed, not asserted: the program's count may change."""
    import flexflow_tpu as ff
    from flexflow_tpu.models.alexnet import build_alexnet

    model = ff.FFModel(ff.FFConfig())
    build_alexnet(model, 1)
    theirs = sum(op.flops_per_sample() for op in model.ops)
    with capsys.disabled():
        print(f"\nAlexNet forward FLOPs a sample: benchmark "
              f"{flops.alexnet_forward():.0f}, program's "
              f"op.flops_per_sample() {theirs:.0f}")
    assert theirs > 0


def test_formula_by_name():
    assert formula("alexnet_train") is flops.alexnet_train
    assert formula("flops.alexnet_train") is flops.alexnet_train
    assert formula("reduce.union").__module__ == "benchmark.reduce"


def test_transformer_flops_by_hand():
    kw = dict(seq_length=1024, num_layers=24, embed_dim=1024, num_heads=16,
              mlp_ratio=4, vocab_size=50257, dropout=0.0)
    matmul = 24 * (4 * 1024 * 1024 + 2 * 4 * 1024 * 1024) + 1024 * 50257
    assert flops.transformer_matmul_params(**kw) == matmul == 353_453_056
    attn_fwd = 2 * 2 * 1024 * 1024 * 1024 / 2       # QK^T and PV, causal half
    assert flops.causal_attention_forward(1024, 1024) == attn_fwd
    want = 6 * matmul * 1024 + 3 * attn_fwd * 24
    assert flops.transformer_train(**kw) == want
    assert want == pytest.approx(2.326e12, rel=1e-3)
    need, nbytes = flops.causal_attention_train(batch=4, **kw)
    assert need == 3 * attn_fwd * 24 * 4
    assert nbytes == 12 * 4 * 1024 * 1024 * 2 * 24


# --------------------------------------------------------------------------
# BENCHMARK.json against the contract's limits: the repo's, and the copy
# that has grown as later PRs grow it (conftest.py), so that none of this
# holds only of the file as it happens to stand
# --------------------------------------------------------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture
def bench(bench_root):
    with open(os.path.join(bench_root, "BENCHMARK.json")) as f:
        return json.load(f)


def test_benchmark_json_keys_and_names(bench, bench_root):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(
        os.path.join(bench_root, "BENCHMARK.json")) < 64 * 1024
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        assert os.path.isfile(os.path.join(bench_root, c["file"]))
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and NAME.match(w["traffic"])
        assert w["config"] in {c["name"] for c in bench["configs"]}
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    metrics = bench["end_to_end"] + bench["per_layer"]
    for group in (bench["configs"], bench["workloads"], metrics):
        names = [x["name"] for x in group]
        assert len(set(names)) == len(names)
        assert all(NAME.match(n) for n in names)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    texts = [x["why"] for x in bench["configs"] + bench["workloads"]] \
        + [c["source"] for c in bench["configs"]] \
        + [m["layer"] for m in bench["per_layer"]] + bench["command"]
    assert all(1 <= len(t) <= 200 and "\n" not in t and "\t" not in t
               for t in texts)
    assert "setup_s" in {m["name"] for m in bench["end_to_end"]}


def test_benchmark_json_cells_and_budget(bench, bench_root):
    cells = bench["workloads"]
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(set(pairs)) == len(pairs)
    assert {w["config"] for w in cells} == {c["name"]
                                            for c in bench["configs"]}
    assert 1 <= len(cells) <= 24 and 1 <= len(bench["configs"]) <= 24
    four = sum(w["chips"] == 4 for w in cells)
    if bench_root == REPO:      # the tests' copy rehearses two such cells
        assert four <= max(1, len(cells) // 4)
    rs = bench["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    # a full check of the full 24 cells fits the driver's 43200 s
    assert (2 + 14 * 24) * (rs + 60) + 24 * 180 + 1200 <= 43200


def test_every_metric_is_reported_where_its_target_is(bench):
    cells = [w["name"] for w in bench["workloads"]]
    e2e = {m["name"]: set(m.get("workloads", cells))
           for m in bench["end_to_end"]}
    for cell in cells:
        assert "setup_s" in {n for n, ws in e2e.items() if cell in ws}
        assert sum(cell in ws for ws in e2e.values()) >= 2
        assert any(cell in m.get("workloads", cells)
                   for m in bench["per_layer"])
    for m in bench["per_layer"]:
        assert set(m.get("workloads", cells)) <= e2e[m["moves"]], m["name"]


def test_the_gpt2_cell_reports_its_rate_per_layer(bench_root):
    """Stalls of seconds in a few runs spread its rate past any bound
    (PERF.md, Findings): end to end it reports the tail and set-up."""
    from benchmark.run import load_cell

    cell = load_cell(bench_root, "gpt2m-train-s1024")
    assert {m["name"] for m in cell["end_to_end"]} == {"step_ms_p90",
                                                       "setup_s"}
    assert {"samples_per_s_per_chip_window",
            "samples_per_s_per_chip_block_median"} <= set(
                cell["layer_metrics"])
    for name in ("alexnet-train-resident", "alexnet-4chip-dp"):
        cell = load_cell(bench_root, name)
        assert {m["name"] for m in cell["end_to_end"]} == {
            "samples_per_s_per_chip", "mfu", "step_ms_p90", "setup_s"}
        assert "samples_per_s_per_chip_window" not in cell["layer_metrics"]


def test_every_per_layer_metric_has_its_reader_file(bench, bench_root):
    home = os.path.join(bench_root, bench["paths"][0])
    layers = set()
    for m in bench["per_layer"]:
        with open(os.path.join(home, "layer_metrics",
                               m["name"] + ".json")) as f:
            spec = json.load(f)
        assert (spec["layer"], spec["unit"], spec["moves"]) == \
            (m["layer"], m["unit"], m["moves"]), m["name"]
        assert os.path.isfile(os.path.join(home, "readers",
                                           spec["reader"] + ".py"))
        layers.add(m["layer"])
    with open(os.path.join(REPO, "PERF.md")) as f:
        perf = f.read()
    assert all(layer in perf for layer in layers if bench_root == REPO)


def test_data_files_are_found_by_name(bench, bench_root):
    home = os.path.join(bench_root, bench["paths"][0])
    for w in bench["workloads"]:
        with open(os.path.join(home, "traffic", w["traffic"] + ".json")) as f:
            traffic = json.load(f)
        assert traffic["reported"] in {v["name"]
                                       for v in traffic["variants"]}
    with open(os.path.join(home, "peaks.json")) as f:
        peaks = json.load(f)
    assert "TPU v5 lite" in peaks and "source" in peaks["TPU v5 lite"]
    for c in bench["configs"]:
        with open(os.path.join(bench_root, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["reduced"] == c["reduced"]
        assert callable(formula(cfg["flops"]))
        assert os.path.isfile(os.path.join(
            home, "reference", cfg.get("reference", c["name"]) + ".py"))
        assert "why" in cfg["loss_tolerance"]
