"""`embedding_ms_per_step`: the entry and its data file, the file's span
against the scopes a tiny transformer's compiled step really carries on
either side of `Embedding`'s rule, and `readers/device_span.py` with that
file on a hand-made trace and on a program older than the scopes.  Nothing
here is a speed."""

import json
import os
import re

import numpy as np
import pytest

import flexflow_tpu as ff
from benchmark import reduce, run
from benchmark.readers import device_span
from flexflow_tpu.models.transformer import build_transformer
from flexflow_tpu.ops import embedding
from flexflow_tpu.runtime import profiling

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = "embedding_ms_per_step"


def _spec():
    with open(os.path.join(REPO, "benchmark", "layer_metrics",
                           NAME + ".json")) as f:
        return json.load(f)


def test_the_entry_and_its_file(bench_root):
    with open(os.path.join(bench_root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"] if m["name"] == NAME)
    spec = _spec()
    assert (entry["layer"], entry["unit"], entry["moves"]) == \
        (spec["layer"], spec["unit"], spec["moves"]) == \
        ("kernels", "ms", "step_ms_p90")
    assert (entry["better"], entry["source"]) == ("lower", "device_trace")
    assert spec["reader"] == "device_span" and "phase" not in spec
    # both cells whose graph has an Embedding
    for cell in ("gpt2m-train-s1024", "dsv2-train-s4096"):
        assert cell in entry["workloads"]
        assert run.load_cell(bench_root, cell)["layer_metrics"][NAME] == spec
    # the AlexNet cells have no Embedding in their graph
    for w in bench["workloads"]:
        if w["config"] == "alexnet":
            assert w["name"] not in entry["workloads"]
            assert NAME not in run.load_cell(
                bench_root, w["name"])["layer_metrics"]


def _step_scopes():
    """The scope map of a tiny transformer's compiled train step."""
    cfg = ff.FFConfig(batch_size=2, compute_dtype="bfloat16")
    cfg.parse_args(["-ll:tpu", "1"])
    m = ff.FFModel(cfg)
    build_transformer(m, 2, seq_length=16, num_layers=1, embed_dim=32,
                      num_heads=2, vocab_size=64)
    m.compile(ff.SGDOptimizer(m, lr=0.01),
              ff.LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
              [ff.MetricsType.ACCURACY])
    m.init_layers(seed=0)
    toks = np.random.default_rng(0).integers(0, 64, (2, 16), dtype=np.int32)
    pos = np.broadcast_to(np.arange(16, dtype=np.int32), (2, 16))
    m.set_batch(dict(zip(m.input_tensors, (toks, pos))),
                np.roll(toks, -1, axis=1))
    step = m._build_train_step()
    step = getattr(step, "fn", step)
    text = step.lower(*m._step_args()[0]).compile().as_text()
    forms = {op.name: op.grad_impl_used[0] for op in m.ops
             if op._type == "Embedding"}
    return profiling.parse_hlo_scopes(text), forms


# the spans and phases the step carries under each form of the gradient
SIDES = {"one_hot_product": {("ff.embed.lookup", "fwd"),
                             ("ff.embed.grad", "bwd")},
         "scatter_add": {("ff.embed.lookup", "fwd"),
                         ("ff.embed.lookup", "bwd")}}


@pytest.mark.parametrize("form", sorted(SIDES))
def test_the_span_finds_both_embeddings_on_either_side_of_the_rule(
        devices, monkeypatch, form):
    # the rule, which at width 32 says scatter_add, forced by the test
    monkeypatch.setattr(embedding, "_table_grad_rule",
                        lambda *a, **kw: (form, "the test's"))
    scopes, forms = _step_scopes()
    assert forms == {"tok_embed": form, "pos_embed": form}
    rx = re.compile(_spec()["span"])
    found = {(e["scope"], e["span"], e["phase"]) for e in scopes.values()
             if e.get("span") and rx.search(e["span"])}
    assert found == {(f"ff.op.embedding.{name}", span, phase)
                     for name in ("tok_embed", "pos_embed")
                     for span, phase in SIDES[form]}, found


# one chip, two runs of `jit_step`: a gather, a product, a scatter-add (the
# lookup's scope, backward phase), a matmul
def _handmade():
    ops = [["%gather_fusion.1 = bf16[8]{0} fusion(%p), kind=kLoop", 0, 30],
           ["%fusion.2 = f32[8]{0} fusion(%p), kind=kOutput", 30, 50],
           ["%fusion.3 = f32[8]{0} fusion(%p), kind=kCustom", 80, 200],
           ["%fusion.4 = f32[8]{0} fusion(%p), kind=kOutput", 280, 100]]
    events = [[n, 1000 + 1000 * run_ + s, d] for run_ in (0, 1)
              for n, s, d in ops]
    modules = [["jit_step(1)", 1000, 400], ["jit_step(1)", 2000, 400]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": modules},
            {"name": "XLA Ops", "events": events}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ["bench.trace_window", 900, 2000]]}]}]}


def _entry(name, phase, span=None):
    e = {"scope": f"ff.op.embedding.{name}", "phase": phase, "kernel": None,
         "mixed": False}
    return dict(e, span=span) if span else e


SCOPES = {"jit_step": [{
    "gather_fusion.1": _entry("tok_embed", "fwd", "ff.embed.lookup"),
    "fusion.2": _entry("pos_embed", "bwd", "ff.embed.grad"),
    "fusion.3": _entry("tok_embed", "bwd", "ff.embed.lookup"),
    "fusion.4": {"scope": "ff.op.linear.lm_head", "phase": "bwd",
                 "kernel": None, "mixed": True}}]}


def test_device_span_sums_lookup_and_gradient(monkeypatch):
    trace = _handmade()
    lines = []
    ctx = run.Context(trace=trace, trace_steps=2, say=lines.append,
                      trace_window=reduce.span_window(trace,
                                                      "bench.trace_window"))
    monkeypatch.setattr(profiling, "step_scopes", lambda: SCOPES)
    # 30 + 50 + 200 ns a step, as ms
    assert device_span.read(ctx, _spec()) == pytest.approx(280e-6)
    assert any("ff.embed.grad.bwd" in ln and "ff.embed.lookup.bwd" in ln
               for ln in lines)
    # the parent's program: the same instructions, no scope inside the op
    older = {"jit_step": [{k: {f: v for f, v in e.items() if f != "span"}
                           for k, e in SCOPES["jit_step"][0].items()}]}
    monkeypatch.setattr(profiling, "step_scopes", lambda: older)
    ctx.__dict__.pop("device_span_join")
    assert device_span.read(ctx, _spec()) is None
