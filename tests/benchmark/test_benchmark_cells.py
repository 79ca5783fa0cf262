"""Every cell of `BENCHMARK.json` against what the harness needs of it,
wherever it stands in the file, and the promise of `benchmark/README.md`,
"a cell goes in as data", as a test: the same checks run on the repo's
benchmark and on a copy that has grown by five cells and five metrics
(`conftest.py`), in which no file that was there was edited.  What holds
of the file as a whole (names, limits, the budget) is in
`test_benchmark_arith.py`.  CPU only, nothing timed."""

import json
import os

from benchmark import run

HARNESS_S_OWN = {"samples_per_s_per_chip", "mfu", "step_ms_p90", "setup_s"}


def _bench(root):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_cell_s_configuration_and_reference(bench_cell):
    root, name = bench_cell
    cell = run.load_cell(root, name)
    entry = next(c for c in _bench(root)["configs"]
                 if c["name"] == cell["config_name"])
    config, traffic = cell["config"], cell["traffic"]
    assert entry["file"].startswith("benchmark/configs/")
    assert entry["reduced"] == config["reduced"]
    # a URL is the same URL in both; a paper may be cited in two wordings
    assert config["source"] and (entry["source"] == config["source"]
                                 or not entry["source"].startswith("http"))
    assert callable(run.resolve(config["builder"]))
    assert run.formula(config["flops"])(**config["builder_kwargs"]) > 0
    ref = run.load_reference(cell["home"],
                             config.get("reference", cell["config_name"]))
    assert callable(ref.make_batch) and callable(ref.loss)
    # `reference_loss` takes CHUNK samples a call: the batch is whole
    # chunks, or one (a reference whose answer depends on the step's whole
    # batch, as a device budget does, says so with a CHUNK above any batch)
    batch = traffic["batch_per_chip"] * cell["chips"]
    assert isinstance(ref.CHUNK, int) and ref.CHUNK >= 1
    assert batch <= ref.CHUNK or batch % ref.CHUNK == 0
    assert 0 < config["loss_tolerance"]["rel"] < 0.01


def test_the_cell_s_traffic_and_end_to_end_metrics(bench_cell):
    root, name = bench_cell
    cell = run.load_cell(root, name)
    traffic = cell["traffic"]
    variants = [v["name"] for v in traffic["variants"]]
    assert traffic["reported"] in variants
    assert len(set(variants)) == len(variants)
    assert traffic["block_min_ms"] > 0 and traffic["batch_per_chip"] >= 1
    if len(variants) > 1:
        assert traffic["segment_blocks"] >= 1
    for v in traffic["variants"]:       # every variant on the cell's chips
        assert v["args"][v["args"].index("-ll:tpu") + 1] == \
            str(cell["chips"])
    # what run.py measures itself: the four, and with two variants the ratio
    ratios = {f"{traffic['reported']}_over_{v}" for v in variants
              if v != traffic["reported"]}
    reports = {m["name"] for m in cell["end_to_end"]}
    assert "setup_s" in reports and len(reports) >= 2
    assert reports <= HARNESS_S_OWN | ratios
    assert ("mfu" in reports) == ("samples_per_s_per_chip" in reports)


def test_the_cell_s_per_layer_metrics(bench_cell):
    root, name = bench_cell
    cell = run.load_cell(root, name)
    entries = {m["name"]: m for m in _bench(root)["per_layer"]}
    reports = {m["name"] for m in cell["end_to_end"]}
    kwargs = cell["config"]["builder_kwargs"]
    assert cell["layer_metrics"]
    for metric, spec in cell["layer_metrics"].items():
        entry = entries[metric]
        assert name in entry.get("workloads", [name])
        assert (spec["layer"], spec["unit"], spec["moves"]) == \
            (entry["layer"], entry["unit"], entry["moves"]), metric
        assert entry["moves"] in reports, metric
        assert os.path.isfile(os.path.join(
            cell["home"], "readers", spec["reader"] + ".py")), metric
        if "formula" in spec:
            flops, nbytes = run.formula(spec["formula"])(
                batch=cell["traffic"]["batch_per_chip"], **kwargs)
            assert flops > 0 and nbytes > 0, metric
        if "time_metric" in spec:       # a roofline's time, in this cell too
            assert spec["time_metric"] in cell["layer_metrics"], metric
            assert entry["unit"] == "%" and metric.endswith("_roofline")


def test_a_cell_goes_in_as_data(grown):
    """The grown copy was made by adding files and entries: every file
    that was there is byte for byte what it was, every entry that was
    there stands where it stood, and all a new cell did to one is append
    its name to the `workloads` list."""
    after = grown.files_after
    assert {k: after[k] for k in grown.files_before} == grown.files_before
    assert len(after) > len(grown.files_before) + 1
    appended = 0
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        assert len(grown.bench[kind]) > len(grown.old[kind])
        for was, now in zip(grown.old[kind], grown.bench[kind]):
            assert {k: v for k, v in now.items() if k != "workloads"} == \
                {k: v for k, v in was.items() if k != "workloads"}
            assert ("workloads" in now) == ("workloads" in was)
            listed = was.get("workloads", [])
            assert now.get("workloads", [])[:len(listed)] == listed
            appended += len(now.get("workloads", [])) > len(listed)
    assert appended >= 12       # the rate, its mfu, the step metrics, ...
    # and it is a benchmark that has outgrown any count a test might pin
    assert grown.bench["per_layer"][-1]["name"] != \
        grown.old["per_layer"][-1]["name"]
    for cell in (w["name"] for w in grown.bench["workloads"]):
        assert run.load_cell(grown.top, cell)["name"] == cell
