"""The benchmark's roots for its own tests: the repo's, and a copy that
has grown the way a later PR grows it, by new files and new entries
alone (`grown`).  A test that holds `BENCHMARK.json` or a cell to the
contract takes `bench_root` and so runs on both: one that pins the
benchmark's size or order fails on the second."""

import hashlib
import json
import os
import shutil
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(__file__), "data")

NEW_FILES = {              # source under data/ -> place under benchmark/
    "alexnet-tiny.json": "configs", "gpt2-tiny.json": "configs",
    "deepseek-v2-tiny.json": "configs",
    "tiny-resident.json": "traffic", "tiny-4dev-searched.json": "traffic",
    "tiny-4dev-dp.json": "traffic",
    "sync_ms_per_block.json": "layer_metrics",
    "search_s.json": "layer_metrics",
    "sim_predicted_searched_over_dp.json": "layer_metrics",
    "embedding_grad_ms_per_step.json": "layer_metrics"}
NEW_CELLS = [("alexnet-tiny.resident", "alexnet-tiny", "tiny-resident", 1),
             ("gpt2-tiny.resident", "gpt2-tiny", "tiny-resident", 1),
             ("alexnet-tiny.4dev", "alexnet-tiny", "tiny-4dev-searched", 4),
             ("alexnet-tiny.4dev-dp", "alexnet-tiny", "tiny-4dev-dp", 4),
             ("dsv2-tiny.resident", "deepseek-v2-tiny", "tiny-resident", 1)]
# the cell of the repo's whose per-layer metrics a new cell of the same
# architecture reads too: it is appended to every list that names that cell
READS_AS = {"gpt2-tiny.resident": "gpt2m-train-s1024",
            "dsv2-tiny.resident": "dsv2-train-s4096"}
# the rate and its mfu list their cells; the DeepSeek-V2 one reports neither
REPORTS_RATE = [c[0] for c in NEW_CELLS if c[1] != "deepseek-v2-tiny"]


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def digests(top):
    out = {}
    for d, _, files in os.walk(top):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, top)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def grow(top):
    """Copy the benchmark to `top` and add five tiny cells, their three
    configurations, one end-to-end metric and four per-layer metrics.
    What a cell edits of an entry that is there is one thing: it appends
    its name to the `workloads` lists it belongs in."""
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(top, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = digests(top)
    for name, where in NEW_FILES.items():
        shutil.copy(os.path.join(DATA, name),
                    os.path.join(top, "benchmark", where, name))
    bench = _json(REPO, "BENCHMARK.json")
    old = json.loads(json.dumps(bench))
    for cfg in sorted({c[1] for c in NEW_CELLS}):
        file = f"benchmark/configs/{cfg}.json"
        spec = _json(top, file)
        bench["configs"].append({
            "name": cfg, "source": spec["source"],
            "reduced": spec["reduced"], "why": "tests", "file": file})
    for name, cfg, traffic, chips in NEW_CELLS:
        bench["workloads"].append({"name": name, "config": cfg,
                                   "traffic": traffic, "chips": chips,
                                   "why": "tests"})
    for m in bench["end_to_end"]:
        if m["name"] in ("samples_per_s_per_chip", "mfu"):
            m["workloads"] = m["workloads"] + REPORTS_RATE
    # a list that names every cell (the program's step metrics) takes the
    # new ones too
    every = {w["name"] for w in old["workloads"]}
    for m in bench["per_layer"]:
        was = m.get("workloads")
        if was:
            m["workloads"] = was + [
                name for name, *_ in NEW_CELLS
                if set(was) == every or READS_AS.get(name) in was]
    # new metrics list their cells
    bench["end_to_end"].append({
        "name": "searched_over_dp", "unit": "ratio", "better": "higher",
        "bound": 0.1, "source": "host_clock",
        "workloads": ["alexnet-tiny.4dev"]})
    bench["per_layer"].append({
        "name": "sync_ms_per_block", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "host step loop",
        "moves": "samples_per_s_per_chip", "workloads": REPORTS_RATE})
    for name, unit, source in (
            ("search_s", "s", "host_clock"),
            ("sim_predicted_searched_over_dp", "ratio", "program_counter")):
        bench["per_layer"].append({
            "name": name, "unit": unit, "better": "lower", "source": source,
            "layer": "strategy search",
            "moves": _json(DATA, name + ".json")["moves"],
            "workloads": ["alexnet-tiny.4dev"]})
    # a second metric of a reader kind that has one, after the entry that
    # was last, for cells that are there and cells that are new
    bench["per_layer"].append({
        "name": "embedding_grad_ms_per_step", "unit": "ms",
        "better": "lower", "source": "device_trace", "layer": "kernels",
        "moves": "step_ms_p90",
        "workloads": ["dsv2-train-s4096", "dsv2-tiny.resident",
                      "gpt2-tiny.resident"]})
    with open(os.path.join(top, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return types.SimpleNamespace(top=top, bench=bench, old=old,
                                 files_before=before, files_after=digests(top))


@pytest.fixture(scope="session")
def grown(tmp_path_factory):
    return grow(str(tmp_path_factory.mktemp("bench_grown")))


def _root(request, kind):
    return REPO if kind == "repo" else request.getfixturevalue("grown").top


@pytest.fixture(params=["repo", "grown"])
def bench_root(request):
    """The root of a benchmark: `BENCHMARK.json` and `benchmark/`."""
    return _root(request, request.param)


def pytest_generate_tests(metafunc):
    """`bench_cell`: every cell of either root, a case each, so that a
    cell a later PR adds is held to the contract by being there."""
    if "bench_cell" in metafunc.fixturenames:
        repo = [w["name"] for w in _json(REPO, "BENCHMARK.json")["workloads"]]
        cells = [("repo", name) for name in repo] + [
            ("grown", name) for name in repo + [c[0] for c in NEW_CELLS]]
        metafunc.parametrize("bench_cell", cells, indirect=True,
                             ids=[f"{kind}:{name}" for kind, name in cells])


@pytest.fixture
def bench_cell(request):
    """(root, name of one of its cells)"""
    kind, name = request.param
    return _root(request, kind), name
