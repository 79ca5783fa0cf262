"""The step's own timeline (runtime/profiling.py): the named scopes in
the compiled step and the scope map read back from the loaded
executables, the compile counter, the program's host spans on the
profiler's clock, and the per-drain rate gauge.  CPU only: nothing here
is a speed."""

import contextlib
import glob
import json
import os
import re

import jax
import numpy as np
import pytest

import flexflow_tpu as ff
from benchmark import reduce
from flexflow_tpu.models.alexnet import build_alexnet
from flexflow_tpu.models.transformer import build_transformer
from flexflow_tpu.observability import events
from flexflow_tpu.observability.stepstats import StepStats
from flexflow_tpu.runtime import profiling

PHASES = {"fwd", "bwd", "opt", "other"}


@pytest.fixture(autouse=True, scope="module")
def _metadata_in_the_cache_key():
    """A program fetched from the persistent cache carries the metadata
    it was compiled with: key the cache by it, as every entry point does
    (utils/compile_cache.py; conftest.py turns it off for the suite)."""
    key = "jax_compilation_cache_include_metadata_in_key"
    old = getattr(jax.config, key)
    jax.config.update(key, True)
    yield
    jax.config.update(key, old)


@pytest.fixture(autouse=True)
def _no_telemetry(monkeypatch):
    events.reset_active()
    monkeypatch.delenv("FF_TELEMETRY", raising=False)
    monkeypatch.delenv("FF_TELEMETRY_FILE", raising=False)
    yield
    events.reset_active()


ACCURACY = (ff.MetricsType.ACCURACY,)


def _conv_net(batch=4, metrics=ACCURACY):
    cfg = ff.FFConfig(batch_size=batch, compute_dtype="float32")
    cfg.parse_args(["-ll:tpu", "1"])
    m = ff.FFModel(cfg)
    build_alexnet(m, batch, num_classes=10, height=67, width=67)
    m.compile(ff.SGDOptimizer(m, lr=0.001),
              ff.LossType.SPARSE_CATEGORICAL_CROSSENTROPY, list(metrics))
    m.init_layers(seed=0)
    rng = np.random.default_rng(0)
    m.set_batch({m.input_tensors[0]:
                 rng.standard_normal((batch, 67, 67, 3), np.float32)},
                rng.integers(0, 10, (batch, 1), dtype=np.int32))
    return m


def _transformer(batch=2, seq=128, metrics=ACCURACY):
    cfg = ff.FFConfig(batch_size=batch, compute_dtype="float32")
    cfg.parse_args(["-ll:tpu", "1"])
    m = ff.FFModel(cfg)
    build_transformer(m, batch, seq_length=seq, num_layers=2, embed_dim=64,
                      num_heads=4, vocab_size=128)
    for op in m.ops:
        if op._type == "MultiHeadAttention":
            op.impl = "pallas_interpret"  # the kernels' scopes, on the CPU
    m.compile(ff.AdamOptimizer(m, alpha=1e-4),
              ff.LossType.SPARSE_CATEGORICAL_CROSSENTROPY, list(metrics))
    m.init_layers(seed=0)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, 128, (batch, seq), dtype=np.int32)
    pos = np.broadcast_to(np.arange(seq, dtype=np.int32), (batch, seq))
    m.set_batch(dict(zip(m.input_tensors, (toks, pos))),
                np.roll(toks, -1, axis=1))
    return m


BUILDERS = {"conv_net": _conv_net, "transformer": _transformer}


def _steps(m, n):
    for _ in range(n):
        m.train_iteration()
    m.sync()
    m.get_metrics()


def _op_scope(op):
    return f"ff.op.{op._type.lower()}.{op.name}"


def _step_hlo(m):
    """The optimized HLO of this model's loaded train step: among the
    client's executables the `jit_step` whose graph-op scopes are the
    model's (other tests' models may still be loaded)."""
    mine = {_op_scope(op) for op in m.ops}
    need = {_op_scope(op) for op in m.ops if op.weights}
    for exe in jax.devices()[0].client.live_executables():
        module = exe.hlo_modules()[0]
        if module.name != "jit_step":
            continue
        text = module.to_string()
        found = set(re.findall(r"ff\.op\.[^/()\"]+", text))
        if need <= found <= mine:
            return text
    raise AssertionError("the model's jit_step is not loaded")


def _entry_instructions(text):
    entry = text[text.index("\nENTRY "):]
    body = entry[entry.index("{\n") + 2:entry.index("\n}")]
    return [re.match(r"\s+(?:ROOT\s+)?%?([\w.\-]+)\s+=", line).group(1)
            for line in body.splitlines()]


# ---------------------------------------------------------------------------
# the scope map
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", sorted(BUILDERS))
def test_every_entry_instruction_has_a_phase(devices, kind):
    m = BUILDERS[kind]()
    _steps(m, 2)
    text = _step_hlo(m)
    scopes = profiling.parse_hlo_scopes(text)
    names = _entry_instructions(text)
    assert len(names) > 20
    for name in names:
        assert scopes[name]["phase"] in PHASES, name
        # `span` where the op opened a scope of its own (ff.embed.*)
        assert set(scopes[name]) - {"span"} == {"scope", "phase", "kernel",
                                                "mixed"}
    # and the process-wide map holds this program under its module's name
    assert any(scopes == loaded
               for loaded in profiling.step_scopes()["jit_step"])


@pytest.mark.parametrize("kind", sorted(BUILDERS))
def test_every_op_with_weights_has_forward_and_backward(devices, kind):
    m = BUILDERS[kind]()
    _steps(m, 1)
    scopes = profiling.parse_hlo_scopes(_step_hlo(m))
    seen = {(e["scope"], e["phase"]) for e in scopes.values()}
    weighted = [op for op in m.ops if op.weights]
    assert len(weighted) >= 8
    for op in weighted:
        scope = _op_scope(op)
        assert (scope, "fwd") in seen, scope
        assert (scope, "bwd") in seen, scope
    assert ("ff.loss", "fwd") in seen and ("ff.loss", "bwd") in seen
    assert any(ph == "opt" for _, ph in seen)


def test_flash_kernels_are_found_by_their_scope(devices):
    m = _transformer()
    _steps(m, 1)
    scopes = profiling.parse_hlo_scopes(_step_hlo(m))
    by_kernel = {}
    for e in scopes.values():
        if e["kernel"]:
            by_kernel.setdefault(e["kernel"], set()).add(
                (e["scope"], e["phase"]))
    assert set(by_kernel) == {"flash_fwd", "flash_dq", "flash_dkv"}
    attn = {f"ff.op.multiheadattention.attn_{i}" for i in (0, 1)}
    assert by_kernel["flash_fwd"] == {(a, "fwd") for a in attn}
    assert by_kernel["flash_dq"] == {(a, "bwd") for a in attn}
    assert by_kernel["flash_dkv"] == {(a, "bwd") for a in attn}


# conv1's weight gradient as the v5e compiler fuses it (PR 25's probe,
# AlexNet at batch 256; shapes and layouts cut): the SGD update is the
# fusion's root, the convolution and a recomputed cast of the batch sit
# inside, the latter in a nested fusion.
WGRAD = '''HloModule jit_step, is_scheduled=true

%fused_computation.8.clone (param_0.1: f32[256,229,229,3]) -> bf16[256,229,229,3] {
  %param_0.1 = f32[256,229,229,3] parameter(0)
  ROOT %convert.1 = bf16[256,229,229,3] convert(%param_0.1), metadata={op_name="jit(step)/jvp(ff.input_cast)/convert_element_type" stack_frame_id=20}
}

%fused_computation.97 (param_0.252: f32[11,11,3,64], param_1.266: f32[], param_2.335: bf16[256,56,56,64], param_4.113: f32[256,229,229,3]) -> f32[11,11,3,64] {
  %param_0.252 = f32[11,11,3,64]{3,2,1,0:T(4,128)S(1)} parameter(0)
  %param_1.266 = f32[]{:T(128)S(6)} parameter(1)
  %mul.32 = f32[11,11,3,64] broadcast(%param_1.266), dimensions={}, metadata={op_name="jit(step)/ff.optimizer/mul" stack_frame_id=47}
  %param_4.113 = f32[256,229,229,3] parameter(3)
  %fusion.105 = bf16[256,229,229,3] fusion(%param_4.113), kind=kLoop, calls=%fused_computation.8.clone, metadata={op_name="jit(step)/jvp(ff.input_cast)/convert_element_type" stack_frame_id=20}
  %param_2.335 = bf16[256,56,56,64] parameter(2)
  %conv_general_dilated.39 = bf16[11,11,3,64] convolution(%fusion.105, %param_2.335), window={size=56x56 pad=2_0x2_0 rhs_dilate=4x4}, dim_labels=f01b_i01o->01bf, metadata={op_name="jit(step)/transpose(jvp(ff.op.conv2d.conv1))/conv_general_dilated" stack_frame_id=22}
  %convert_element_type.117 = f32[11,11,3,64] convert(%conv_general_dilated.39), metadata={op_name="jit(step)/transpose(jvp(ff.op.conv2d.conv1))/convert_element_type" stack_frame_id=21}
  %mul.28 = f32[11,11,3,64] multiply(%mul.32, %convert_element_type.117), metadata={op_name="jit(step)/ff.optimizer/mul" stack_frame_id=47}
  ROOT %sub.23 = f32[11,11,3,64]{3,2,1,0:T(4,128)S(1)} subtract(%param_0.252, %mul.28), metadata={op_name="jit(step)/ff.optimizer/sub" stack_frame_id=48}
}

%fused_computation.3 (param_0.9: f32[10], param_1.9: f32[10], param_2.9: f32[]) -> f32[10] {
  %param_0.9 = f32[10] parameter(0)
  %param_1.9 = f32[10] parameter(1)
  %param_2.9 = f32[] parameter(2)
  %mul.2 = f32[10] broadcast(%param_2.9), dimensions={}, metadata={op_name="jit(step)/ff.optimizer/mul"}
  %mul.3 = f32[10] multiply(%mul.2, %param_1.9), metadata={op_name="jit(step)/ff.optimizer/mul"}
  ROOT %sub.3 = f32[10] subtract(%param_0.9, %mul.3), metadata={op_name="jit(step)/ff.optimizer/sub"}
}

%region_0.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.1 = f32[] add(%a, %b), metadata={op_name="jit(step)/ff.metrics/reduce_sum"}
}

ENTRY %main.47 (w: f32[11,11,3,64], lr: f32[], dy: bf16[256,56,56,64], x: f32[256,229,229,3], b: f32[10], db: f32[10]) -> (f32[11,11,3,64], f32[10], f32[]) {
  %w = f32[11,11,3,64] parameter(0), metadata={op_name="params['conv1']['kernel']"}
  %lr = f32[] parameter(1)
  %dy = bf16[256,56,56,64] parameter(2)
  %x = f32[256,229,229,3] parameter(3)
  %b = f32[10] parameter(4)
  %db = f32[10] parameter(5)
  %copy-start.1 = (f32[10], f32[10], u32[]) copy-start(%b)
  %copy-done.1 = f32[10] copy-done(%copy-start.1)
  %multiply_subtract_fusion.7 = f32[11,11,3,64]{3,2,1,0:T(4,128)S(1)} fusion(%w, %lr, %dy, %x), kind=kOutput, calls=%fused_computation.97, metadata={op_name="jit(step)/transpose(jvp(ff.op.conv2d.conv1))/conv_general_dilated" stack_frame_id=22}
  %multiply_subtract_fusion.14 = f32[10] fusion(%copy-done.1, %db, %lr), kind=kLoop, calls=%fused_computation.3
  %reduce.5 = f32[] reduce(%db, %lr), dimensions={0}, to_apply=%region_0.1, metadata={op_name="jit(step)/ff.metrics/reduce_sum"}
  %flash_dq.3 = bf16[32,1024,64] custom-call(%dy), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp(ff.op.multiheadattention.attn_0))/ff.kernel.flash_dq/pallas_call"}
  %remat.1 = f32[10] add(%b, %db), metadata={op_name="jit(step)/transpose(jvp(ff.op.dense.fc1))/checkpoint/rematted_computation/add"}
  ROOT %tuple.1 = (f32[11,11,3,64], f32[10], f32[]) tuple(%multiply_subtract_fusion.7, %multiply_subtract_fusion.14, %reduce.5)
}
'''


def test_parse_rules_on_a_weight_gradient_fusion():
    scopes = profiling.parse_hlo_scopes(WGRAD)
    # the device runs the entry's instructions, not those inside a
    # fused computation or a reducer
    assert set(scopes) == {
        "w", "lr", "dy", "x", "b", "db", "copy-start.1", "copy-done.1",
        "multiply_subtract_fusion.7", "multiply_subtract_fusion.14",
        "reduce.5", "flash_dq.3", "remat.1", "tuple.1"}
    # the update fused into the weight gradient stays the gradient's: by
    # the convolution inside, not by the root (ff.optimizer/sub)
    assert scopes["multiply_subtract_fusion.7"] == {
        "scope": "ff.op.conv2d.conv1", "phase": "bwd", "kernel": None,
        "mixed": True}
    # an update that stands alone is the optimizer's, by its root
    assert scopes["multiply_subtract_fusion.14"] == {
        "scope": "ff.optimizer", "phase": "opt", "kernel": None,
        "mixed": False}
    assert scopes["reduce.5"]["phase"] == "other"
    assert scopes["reduce.5"]["scope"] == "ff.metrics"
    assert scopes["flash_dq.3"] == {
        "scope": "ff.op.multiheadattention.attn_0", "phase": "bwd",
        "kernel": "flash_dq", "mixed": False}
    # the forward recomputed under jax.checkpoint is spent in backward
    assert scopes["remat.1"]["phase"] == "bwd"
    # the compiler's own instructions carry no op_name: an asynchronous
    # copy is put down to the instruction that waits for it
    for name in ("copy-start.1", "copy-done.1"):
        assert scopes[name] == scopes["multiply_subtract_fusion.14"]
    assert scopes["w"] == {"scope": None, "phase": "other", "kernel": None,
                           "mixed": False}  # named, but by no scope
    # a copy out to the result has no user but the root: by its operand
    out = WGRAD.replace(
        "  ROOT %tuple.1 =",
        "  %copy-start.2 = (f32[10], f32[10], u32[]) copy-start("
        "%multiply_subtract_fusion.14)\n"
        "  %copy-done.2 = f32[10] copy-done(%copy-start.2)\n"
        "  ROOT %tuple.1 =").replace(
        "%multiply_subtract_fusion.14, %reduce.5)", "%copy-done.2, %reduce.5)")
    scopes = profiling.parse_hlo_scopes(out)
    for name in ("copy-start.2", "copy-done.2"):
        assert scopes[name] == scopes["multiply_subtract_fusion.14"]


@pytest.mark.parametrize("op_name,phase,scope,kernel", [
    ("jit(step)/jvp(ff.op.conv2d.conv1)/conv_general_dilated", "fwd",
     "ff.op.conv2d.conv1", None),
    ("jit(step)/transpose(jvp(ff.op.dense.fc1))/dot_general", "bwd",
     "ff.op.dense.fc1", None),
    ("jit(step)/jvp(ff.loss)/jit(log_softmax)/reduce_max", "fwd",
     "ff.loss", None),
    ("jit(step)/transpose(jvp(ff.loss))/mul", "bwd", "ff.loss", None),
    ("jit(step)/jvp(ff.input_cast)/convert_element_type", "fwd",
     "ff.input_cast", None),
    ("jit(step)/ff.optimizer/sub", "opt", "ff.optimizer", None),
    ("jit(step)/ff.metrics/add", "other", "ff.metrics", None),
    ("jit(step)/ff.guard/select_n", "other", "ff.guard", None),
    ("jit(step)/jvp(ff.op.multiheadattention.a)/ff.kernel.flash_fwd/"
     "pallas_call", "fwd", "ff.op.multiheadattention.a", "flash_fwd"),
    ("jit(estep)/ff.op.dense.fc1/dot_general", "fwd", "ff.op.dense.fc1",
     None),
    ("params['fc1']['kernel']", "other", None, None),
    ("", "other", None, None),
])
def test_phase_of_an_op_name(op_name, phase, scope, kernel):
    assert profiling.scope_of(op_name) == {"scope": scope, "phase": phase,
                                           "kernel": kernel}


def test_remat_recomputation_counts_as_backward(devices):
    cfg = ff.FFConfig(batch_size=8, compute_dtype="float32")
    cfg.parse_args(["-ll:tpu", "1"])
    cfg.remat = True
    m = ff.FFModel(cfg)
    inp = m.create_tensor((8, 16), nchw=False)
    t = m.dense(inp, 32, activation=ff.ActiMode.RELU, name="fc1")
    m.softmax(m.dense(t, 4, name="fc2"))
    m.compile(ff.SGDOptimizer(m, lr=0.1),
              ff.LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
              [ff.MetricsType.ACCURACY])
    m.init_layers(seed=0)
    rng = np.random.default_rng(0)
    m.set_batch({inp: rng.standard_normal((8, 16), np.float32)},
                rng.integers(0, 4, (8, 1), dtype=np.int32))
    _steps(m, 1)
    text = _step_hlo(m)
    names = re.findall(r'op_name="([^"]*)"', text)
    remat = [n for n in names if "rematted_computation" in n]
    assert remat, "the step recomputes nothing"
    for n in remat:
        assert profiling.scope_of(n)["phase"] == "bwd", n


def _canonical(text):
    """Optimized HLO without what a scope may change: metadata, the
    tables of source locations it points into, and the names of
    instructions and computations (numbered in order of appearance)."""
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    text = re.sub(r"^(?:FileNames|FunctionNames|FileLocations|StackFrames)\n"
                  r"(?:.+\n)*", "", text, flags=re.M)
    text = re.sub(r"in_\d+", "in_N", text)  # a tensor's process-wide guid
    names = {}

    def number(match):
        return names.setdefault(match.group(0), f"%n{len(names)}")

    return re.sub(r"%[\w.\-]+", number, text)


def _compiled_step(m):
    """Optimized HLO of the model's train step, traced and compiled now
    for its live arguments."""
    return m._train_step_fn.lower(*m._step_args()[0]).compile().as_text()


@pytest.mark.parametrize("kind", sorted(BUILDERS))
def test_scopes_change_nothing_that_is_computed(devices, monkeypatch, kind):
    with_scopes = BUILDERS[kind]()
    _steps(with_scopes, 1)
    scoped = _compiled_step(with_scopes)
    assert "ff.op." in scoped
    with monkeypatch.context() as mp:
        mp.setattr(jax, "named_scope",
                   lambda name: contextlib.nullcontext())
        without = BUILDERS[kind]()
        _steps(without, 1)
        bare = _compiled_step(without)
    assert "ff." not in bare and bare != scoped
    assert _canonical(bare) == _canonical(scoped)
    assert with_scopes.last_loss == without.last_loss


# ---------------------------------------------------------------------------
# the step reads its logits once
# ---------------------------------------------------------------------------

SPARSE_CCE = ff.MetricsType.SPARSE_CATEGORICAL_CROSSENTROPY
MSE = ff.MetricsType.MEAN_SQUARED_ERROR
MAE = ff.MetricsType.MEAN_ABSOLUTE_ERROR


def _under(scopes, scope):
    return [name for name, e in scopes.items() if e["scope"] == scope]


@pytest.mark.parametrize("kind,metrics,softmax_runs", [
    ("transformer", ACCURACY, False),
    ("conv_net", ACCURACY, False),
    ("transformer", ACCURACY + (SPARSE_CCE,), False),
    ("transformer", ACCURACY + (MSE,), True),   # needs the probabilities
    ("transformer", (MAE,), True),
])
def test_train_step_runs_the_final_softmax_only_for_a_metric_that_needs_it(
        devices, kind, metrics, softmax_runs):
    m = BUILDERS[kind](metrics=metrics)
    _steps(m, 1)
    # compiled anew for the live arguments: models of the same op names
    # may still be loaded, and this one's program must be among them
    text = _compiled_step(m)
    scopes = profiling.parse_hlo_scopes(text)
    assert any(scopes == loaded
               for loaded in profiling.step_scopes()["jit_step"])
    final = _op_scope(m.ops[-1])
    assert final.startswith("ff.op.softmax.")
    # anywhere in the program, inside a fusion too: on f32 logits the
    # Softmax's exponent and sum are the loss's own (losses.neg_log_prob
    # does the same arithmetic, so XLA computes them once), and what is
    # left of it, the division, fuses into the metric that reads it
    assert (final in text) is softmax_runs
    assert _under(scopes, "ff.metrics") and _under(scopes, "ff.loss")


def test_eval_step_returns_the_probabilities_so_its_softmax_runs(devices):
    m = _transformer()
    m.eval_batch()
    params, batch = m._eval_inputs()
    text = m._eval_step_fn.lower(params, m._stats, batch).compile().as_text()
    scopes = profiling.parse_hlo_scopes(text)
    assert _under(scopes, _op_scope(m.ops[-1]))
    assert _under(scopes, "ff.metrics") and _under(scopes, "ff.loss")


# The first step's loss at the parent of the PR that moved the metrics to
# the logits (PR 28; CPU backend, float32): the loss's arithmetic is not
# touched by what the metrics read.
FIRST_LOSS = {"conv_net": 2.3102827072143555,
              "transformer": 5.051053524017334}


@pytest.mark.parametrize("kind", sorted(BUILDERS))
def test_first_loss_is_what_it_was_before_the_metrics_moved(devices, kind):
    m = BUILDERS[kind]()
    _steps(m, 1)
    assert m.last_loss == FIRST_LOSS[kind]


def _seq_mlp(accum=1, metrics=ACCURACY + (SPARSE_CCE,)):
    cfg = ff.FFConfig(batch_size=8, compute_dtype="float32")
    cfg.parse_args(["-ll:tpu", "1", "--grad-accum", str(accum)])
    m = ff.FFModel(cfg)
    x = m.create_tensor((8, 6, 16), nchw=False)
    t = m.dense(x, 32, activation=ff.ActiMode.RELU, name="fc1")
    m.softmax(m.dense(t, 12, name="fc2"))
    m.compile(ff.SGDOptimizer(m, lr=0.1),
              ff.LossType.SPARSE_CATEGORICAL_CROSSENTROPY, list(metrics))
    m.init_layers(seed=0)
    rng = np.random.default_rng(0)
    m.set_batch({x: rng.standard_normal((8, 6, 16), np.float32)},
                rng.integers(0, 12, (8, 6), dtype=np.int32))
    return m


@pytest.mark.parametrize("metrics", [ACCURACY + (SPARSE_CCE,),
                                     ACCURACY + (SPARSE_CCE, MSE)],
                         ids=["from_logits", "from_probabilities"])
@pytest.mark.parametrize("how", ["step_accum", "eval"])
def test_accumulation_and_eval_count_what_the_step_counts(devices, how,
                                                          metrics):
    m = _seq_mlp(metrics=metrics)
    _steps(m, 1)
    want = m.current_metrics
    assert want.train_all == 48 and 0 < want.train_correct < 48
    if how == "eval":  # before any update: the step's own forward pass
        got = _seq_mlp(metrics=metrics).eval_batch()
        got_loss = got["loss"]
    else:
        other = _seq_mlp(accum=2, metrics=metrics)
        _steps(other, 1)
        got, got_loss = vars(other.current_metrics), other.last_loss
    assert got["train_all"] == want.train_all
    assert got["train_correct"] == want.train_correct
    assert got["sparse_cce_loss"] == pytest.approx(want.sparse_cce_loss,
                                                   rel=1e-5)
    if MSE in metrics:
        assert want.mse_loss > 0
        assert got["mse_loss"] == pytest.approx(want.mse_loss, rel=1e-5)
    assert got_loss == pytest.approx(m.last_loss, rel=1e-6)
    # the metric and the loss are one quantity, summed and averaged
    assert want.sparse_cce_loss / 48 == pytest.approx(m.last_loss, rel=1e-5)


# ---------------------------------------------------------------------------
# the compile counter
# ---------------------------------------------------------------------------

def test_train_step_compiles_counts_compilations_not_steps(devices):
    fired = []
    inside = [False]

    def listener(event, secs, **_):
        if inside[0] and event == "/jax/core/compile/backend_compile_duration":
            fired.append(secs)

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        m = _conv_net()
        step = m._train_step_fn = m._build_train_step()

        def watched(*args):
            inside[0] = True
            try:
                return step(*args)
            finally:
                inside[0] = False

        m._train_step_fn = watched
        before = profiling.counters()
        for i in range(10):
            m.train_iteration()
            if i % 3 == 2:
                m.get_metrics()  # a fresh accumulator, placed as the step's
        after = profiling.counters()
        # the small programs update() dispatches beside the step (the
        # step's index, the hyper-parameters) are not the step's
        assert after["train_step_compiles"] \
            - before["train_step_compiles"] == len(fired) >= 1
        assert after["train_step_compile_s"] \
            - before["train_step_compile_s"] == pytest.approx(sum(fired))
        for i in range(10):
            m.train_iteration()
            if i % 3 == 2:
                m.get_metrics()
        assert profiling.counters() == after
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)


# ---------------------------------------------------------------------------
# host spans
# ---------------------------------------------------------------------------

def test_no_event_log_call_without_telemetry(devices, monkeypatch, tmp_path):
    monkeypatch.chdir(tmp_path)

    def refuse(self, *a, **kw):
        raise AssertionError("event-log call with telemetry off")

    for name in ("_write", "span", "span_at", "counter", "gauge", "event"):
        monkeypatch.setattr(events.EventLog, name, refuse)
    m = _conv_net()
    assert m._telemetry is None and m._stepstats is None
    for _ in range(3):
        m.update()
    m.sync()
    m.get_metrics()
    assert not os.path.exists("ff_trace.jsonl")


def _host_spans(logdir):
    path = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    return reduce.host_spans(reduce.load(path), "ff.")


def test_spans_nest_on_the_profilers_clock(devices, tmp_path):
    m = _conv_net()
    logdir = str(tmp_path / "trace")
    with profiling.trace(logdir):
        for _ in range(3):
            m.train_iteration()
        m.sync()
        m.get_metrics()
        m.train_iteration()
    spans = _host_spans(logdir)
    by_name = {}
    for name, s, e in spans:
        by_name.setdefault(name, []).append((s, e))
    assert {n: len(v) for n, v in by_name.items()} == {
        "ff.update": 4, "ff.step_build": 1, "ff.update.prepare": 4,
        "ff.update.enqueue": 4, "ff.update.finish": 4, "ff.sync": 1,
        "ff.metric_drain": 1}

    def parent(child, name):
        return [p for p in by_name[name] if p[0] <= child[0]
                and child[1] <= p[1]]

    for inner in ("ff.update.prepare", "ff.update.enqueue",
                  "ff.update.finish"):
        for i, child in enumerate(by_name[inner]):
            assert parent(child, "ff.update") == [by_name["ff.update"][i]]
    build = by_name["ff.step_build"][0]
    assert parent(build, "ff.update") == [by_name["ff.update"][0]]
    assert parent(by_name["ff.update.enqueue"][0], "ff.step_build")
    assert not parent(by_name["ff.update.enqueue"][1], "ff.step_build")
    for i in range(4):
        p, q, f = (by_name[n][i] for n in ("ff.update.prepare",
                                           "ff.update.enqueue",
                                           "ff.update.finish"))
        assert p[1] <= q[0] and q[1] <= f[0]
    for name in ("ff.sync", "ff.metric_drain"):
        assert not parent(by_name[name][0], "ff.update")
    # the scope map of the programs loaded when the trace ended
    with open(os.path.join(logdir, profiling.SCOPES_FILE)) as f:
        saved = json.load(f)
    assert any(e["scope"] == "ff.op.conv2d.conv1"
               for prog in saved["jit_step"] for e in prog.values())


def test_other_spans_are_on_the_profilers_clock_too(devices, tmp_path):
    m = _conv_net()
    rng = np.random.default_rng(1)
    dl = ff.DataLoader(
        m, {m.input_tensors[0]:
            rng.standard_normal((8, 67, 67, 3), np.float32)},
        rng.integers(0, 10, (8, 1), dtype=np.int32))
    logdir = str(tmp_path / "trace")
    with profiling.trace(logdir):
        dl.next_batch(m)
        m.train_iteration()
        m.save(str(tmp_path / "ckpt.npz"))
        m.load(str(tmp_path / "ckpt.npz"))
    names = {n for n, _, _ in _host_spans(logdir)}
    assert {"ff.data_wait", "ff.checkpoint_save",
            "ff.checkpoint_restore", "ff.update"} <= names


def test_span_opens_the_logs_span_when_a_log_is_active(tmp_path):
    log = events.EventLog(str(tmp_path / "t.jsonl"))
    with profiling.span(log, "compile", num_ops=3) as at:
        at["num_devices"] = 8
        with profiling.span(log, "update.prepare"):
            pass
    with profiling.span(None, "sync") as at:
        at["ignored"] = True
    log.close()
    with open(tmp_path / "t.jsonl") as f:
        recs = [json.loads(line) for line in f][1:]
    assert [(r["name"], r["parent"]) for r in recs] == [
        ("update.prepare", 1), ("compile", None)]
    assert recs[1]["attrs"] == {"num_ops": 3, "num_devices": 8}


# ---------------------------------------------------------------------------
# the per-drain rate
# ---------------------------------------------------------------------------

class _Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


@pytest.mark.parametrize("k,seconds", [(1, 0.5), (4, 2.0), (7, 0.25)])
def test_rate_is_gauged_once_a_drain_over_the_drain_interval(
        devices, tmp_path, k, seconds):
    clock = _Clock()
    log = events.EventLog(str(tmp_path / "t.jsonl"), clock=clock)
    m = _conv_net(batch=4)
    stats = StepStats(m, log)

    def enqueue():
        clock.t += 0.001  # an enqueue is quick, whatever the device takes

    for _ in range(3):
        stats.timed_update(enqueue)
    stats.on_drain()  # the first drain starts the clock: no gauge
    for _ in range(2):
        for _ in range(k):
            stats.timed_update(enqueue)
        clock.t += seconds - k * 0.001  # the device finishes; the drain reads
        stats.on_drain()
    stats.on_drain()  # a drain with no step since the last: no gauge
    log.close()
    with open(tmp_path / "t.jsonl") as f:
        recs = [json.loads(line) for line in f]
    rates = [r["v"] for r in recs
             if r["t"] == "gauge" and r["name"] == "samples_per_sec"]
    assert rates == [round(k * 4 / seconds, 2)] * 2
    per_chip = [r["v"] for r in recs if r["t"] == "gauge"
                and r["name"] == "samples_per_sec_per_chip"]
    assert per_chip == rates  # one device
    assert not [r for r in recs if r.get("name") == "mfu"]  # off the TPU
    steps = [r for r in recs if r["t"] == "span" and r["name"] == "step"]
    assert len(steps) == 3 + 2 * k
    for s in steps:
        assert set(s["attrs"]) == {"step", "first", "trace_id", "batch_size"}
