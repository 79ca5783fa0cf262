"""Set-up from inside the program (runtime/profiling.py): phases on the
host's clock, JAX's compile-pipeline events put down to the phase they
fired in, the step's call as a phase on condition, what lies before the
first model, and the one pair of `jax.monitoring` listeners that counts
it all.  Nothing here is a speed."""

import hashlib
import json
import subprocess
import sys
import threading
import time

import jax
import numpy as np
import pytest
from jax._src import monitoring

import flexflow_tpu as ff
from flexflow_tpu.observability import events
from flexflow_tpu.parallel.mesh import Machine
from flexflow_tpu.runtime import profiling
from flexflow_tpu.utils import compile_cache

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
STAGES = ("trace", "lower", "backend")


@pytest.fixture(autouse=True)
def _no_telemetry(monkeypatch):
    events.reset_active()
    monkeypatch.delenv("FF_TELEMETRY", raising=False)
    monkeypatch.delenv("FF_TELEMETRY_FILE", raising=False)
    yield
    events.reset_active()


def _mlp(batch=8, width=8, telemetry=False, n_devices=1):
    cfg = ff.FFConfig(batch_size=batch, compute_dtype="float32")
    cfg.parse_args(["-ll:tpu", str(n_devices)])
    cfg.telemetry = telemetry
    m = ff.FFModel(cfg)
    t = m.dense(m.create_tensor((batch, width), nchw=False), 4, name="fc")
    m.softmax(t, name="sm")
    m.compile(ff.SGDOptimizer(m, lr=0.1),
              ff.LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
              [ff.MetricsType.ACCURACY])
    return m


def _staged(m, batch=8, width=8):
    m.init_layers(seed=0)
    return _with_batch(m, batch, width)


def _with_batch(m, batch=8, width=8):
    rng = np.random.default_rng(0)
    m.set_batch({m.input_tensors[0]:
                 rng.standard_normal((batch, width), np.float32)},
                rng.integers(0, 4, (batch, 1), dtype=np.int32))
    return m


def _delta(after, before):
    return {k: v - before.get(k, 0.0) for k, v in after.items()
            if v != before.get(k, 0.0)}


# ---------------------------------------------------------------------------
# phases and plain spans
# ---------------------------------------------------------------------------

def test_a_phase_counts_its_seconds_and_a_plain_span_does_not():
    before = profiling.counters()
    with profiling.span(None, "t_plain"):
        time.sleep(0.01)
    assert profiling.counters() == before
    for _ in range(2):
        with profiling.phase(None, "t_phase", why="test") as at:
            assert at == {"why": "test"}
            time.sleep(0.02)
    got = _delta(profiling.counters(), before)
    assert set(got) == {"span_s.t_phase", "span_n.t_phase"}
    assert got["span_n.t_phase"] == 2
    assert 0.04 <= got["span_s.t_phase"] < 1.0


def test_a_phase_that_raises_is_counted_and_closed():
    before = profiling.counters().get("span_n.t_raises", 0)
    with pytest.raises(RuntimeError):
        with profiling.phase(None, "t_raises"):
            raise RuntimeError("inside")
    assert profiling.counters()["span_n.t_raises"] == before + 1
    assert profiling._thread.phases == []


def test_a_phase_is_the_logs_span_too(tmp_path):
    log = events.EventLog(str(tmp_path / "t.jsonl"))
    with profiling.phase(log, "t_logged", n=1) as at:
        at["m"] = 2
    log.close()
    with open(tmp_path / "t.jsonl") as f:
        recs = [json.loads(line) for line in f][1:]
    assert [(r["name"], r["attrs"]) for r in recs] == [
        ("t_logged", {"n": 1, "m": 2})]


def test_init_layers_is_a_phase_counted_once_a_model(devices, tmp_path,
                                                     monkeypatch):
    monkeypatch.setenv("FF_TELEMETRY_FILE", str(tmp_path / "run.jsonl"))
    before = profiling.counters()
    m = _mlp(telemetry=True)
    m.init_layers(seed=0)
    got = _delta(profiling.counters(), before)
    assert got["span_n.compile"] == 1 and got["span_n.init_layers"] == 1
    assert got["span_s.init_layers"] > 0
    # the jitted init was traced, lowered and compiled (or fetched) here
    for stage in STAGES:
        assert got[f"stage_n.init_layers.{stage}"] >= 1
        assert got[f"stage_s.init_layers.{stage}"] > 0
    assert not any(k.startswith("train_step") for k in got)
    m._telemetry.flush()
    with open(m._telemetry.path) as f:
        names = [r.get("name") for r in map(json.loads, f)
                 if r.get("t") == "span"]
    assert names.count("init_layers") == 1 and "compile" in names
    _mlp().init_layers(seed=1)
    assert _delta(profiling.counters(), before)["span_n.init_layers"] == 2


# ---------------------------------------------------------------------------
# JAX's events, by the phase open where they fired
# ---------------------------------------------------------------------------

def test_a_program_compiled_outside_every_phase_is_nobodys(devices):
    before = profiling.counters()
    jax.jit(lambda x: x * 5 - 2)(np.ones((3, 11))).block_until_ready()
    got = _delta(profiling.counters(), before)
    assert set(got) >= {f"stage_{k}.none.{s}" for k in "sn" for s in STAGES}
    assert all(k.split(".")[1] == "none" for k in got), got
    assert got["stage_n.none.trace"] == got["stage_n.none.lower"] \
        == got["stage_n.none.backend"] == 1


def test_the_innermost_open_phase_takes_the_event(devices):
    before = profiling.counters()
    with profiling.phase(None, "t_outer"):
        with profiling.phase(None, "t_inner"):
            jax.jit(lambda x: x * 7 - 3)(np.ones((3, 13))).block_until_ready()
        jax.jit(lambda x: x * 7 - 4)(np.ones((3, 13))).block_until_ready()
    got = _delta(profiling.counters(), before)
    assert got["stage_n.t_inner.backend"] == 1
    assert got["stage_n.t_outer.backend"] == 1
    assert not any(".none." in k for k in got)


def test_nested_traces_are_not_summed(devices):
    """JAX fires a trace event for every `jax.jit` it traces, an inner
    one inside its caller's: the program's trace seconds are the
    outermost's, which holds the inner's."""
    nap = 0.4

    @jax.jit
    def inner(x):
        time.sleep(nap)  # runs while traced only
        return x * 2 + 1

    @jax.jit
    def outer(x):
        return inner(x) + inner(x * 3)  # traced once: the second is cached

    fired = []

    def listener(event, secs, **_):
        if event == TRACE:
            fired.append(secs)

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        before = profiling.counters()
        with profiling.phase(None, "t_nested"):
            outer(np.ones((5, 3))).block_until_ready()
        got = _delta(profiling.counters(), before)
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    slept = [s for s in fired if s >= nap]
    assert len(slept) >= 2 and sum(slept) >= 2 * nap  # inner's and outer's
    assert got["stage_n.t_nested.trace"] == got["stage_n.t_nested.lower"] == 1
    assert got["stage_s.t_nested.trace"] == pytest.approx(max(fired))
    assert nap <= got["stage_s.t_nested.trace"] < 2 * nap
    assert got["stage_s.t_nested.trace"] + got["stage_s.t_nested.lower"] \
        + got["stage_s.t_nested.backend"] <= got["span_s.t_nested"]


def test_a_trace_made_while_lowering_is_not_the_programs():
    """By hand, through JAX's own recorder: an outer trace of 0.3 s, then
    a lowering of 60 ms inside which a lowering rule traced something for
    10 ms.  The program's trace is the last that began before its
    lowering did."""
    before = profiling.counters()
    with profiling.phase(None, "t_byhand"):
        monitoring.record_event_duration_secs(TRACE, 0.02)  # an inner jit
        monitoring.record_event_duration_secs(TRACE, 0.3)   # the program's
        time.sleep(0.05)
        monitoring.record_event_duration_secs(TRACE, 0.01)  # a rule's
        monitoring.record_event_duration_secs(LOWER, 0.06)
        # a lowering with no trace of its own (another sharding of a
        # traced program) takes none
        monitoring.record_event_duration_secs(LOWER, 0.001)
    got = _delta(profiling.counters(), before)
    assert got["stage_s.t_byhand.trace"] == pytest.approx(0.3)
    assert got["stage_n.t_byhand.trace"] == 1
    assert got["stage_s.t_byhand.lower"] == pytest.approx(0.061)
    assert got["stage_n.t_byhand.lower"] == 2
    assert profiling._thread.traces == []


def test_events_land_in_the_phase_of_their_own_thread(devices):
    """Eight threads, each in a phase of its own, compile three programs
    each while the main thread compiles outside every phase: no event is
    lost, and none lands in another thread's phase."""
    n_threads, n_programs = 8, 3
    before = profiling.counters()
    errors = []

    def work(i):
        try:
            for j in range(n_programs):
                with profiling.phase(None, f"t_thread{i}"):
                    jax.jit(lambda x: x * (i + 2) + j)(
                        np.ones((2, 17 + i))).block_until_ready()
        except Exception as e:  # read below: a thread must not fail silently
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        jax.jit(lambda x: x - 11)(np.ones((2, 41))).block_until_ready()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert not errors and not any(t.is_alive() for t in threads)
    got = _delta(profiling.counters(), before)
    for i in range(n_threads):
        assert got[f"span_n.t_thread{i}"] == n_programs
        for stage in STAGES:
            assert got[f"stage_n.t_thread{i}.{stage}"] == n_programs
    assert got["stage_n.none.backend"] == 1


# ---------------------------------------------------------------------------
# the step's call
# ---------------------------------------------------------------------------

def test_a_steady_enqueue_changes_no_counter():
    before = profiling.counters()
    for _ in range(3):
        with profiling.step_enqueue(None):
            pass
    assert profiling.counters() == before
    assert profiling._thread.phases == []


def test_the_steps_calls_hold_their_trace_lowering_and_backend(devices):
    m = _staged(_mlp(width=24), width=24)
    before = profiling.counters()
    for i in range(4):
        m.train_iteration()
        if i == 1:
            m.get_metrics()  # a fresh accumulator, placed as the step's
    m.sync()
    after = profiling.counters()
    got = _delta(after, before)
    # one call that traced, lowered and compiled: the step has one program
    assert got["train_step_compiles"] == 1
    assert got["train_step_compile_calls"] == 1
    assert got["stage_n.update.enqueue.trace"] == 1
    assert got["stage_n.update.enqueue.lower"] == 1
    parts = [got["train_step_trace_s"], got["train_step_lower_s"],
             got["train_step_compile_s"]]
    assert all(p > 0 for p in parts)
    assert sum(parts) <= got["train_step_compile_call_s"]
    # inside the build's phase
    assert got["span_n.step_build"] == 1
    # the names the benchmark reads are the stages under update.enqueue
    assert after["train_step_trace_s"] == after["stage_s.update.enqueue.trace"]
    assert after["train_step_compile_s"] \
        == after["stage_s.update.enqueue.backend"]
    # and steady steps and a drain change nothing
    for _ in range(3):
        m.train_iteration()
    m.sync()
    m.get_metrics()
    m.train_iteration()
    assert profiling.counters() == after


def _digest(text):
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("n_devices", [1, 4])
def test_a_step_function_has_one_signature_for_its_life(devices, n_devices):
    """Every array the first call receives is placed as the step hands
    it back, so no later call (after a drain, a `reset_metrics()`, a
    `recompile`) meets the jit's cache with another signature."""
    m = _staged(_mlp(width=24, n_devices=n_devices), width=24)
    before = profiling.counters()

    def step(compiles):
        m.train_iteration()
        assert m._train_step_fn._cache_size() == 1
        got = _delta(profiling.counters(), before)
        assert got["train_step_compiles"] == compiles
        assert got["stage_n.update.enqueue.trace"] == compiles
        assert got["stage_n.update.enqueue.lower"] == compiles

    for _ in range(3):  # the first step and two steady ones
        step(1)
    acc = m._metric_acc
    assert acc.committed and acc.sharding == m.machine.replicated()
    assert m.get_metrics().train_all == 3 * 8
    step(1)  # after a drain
    m.reset_metrics()
    assert m._metric_acc is None
    lowered = _digest(m.train_step_hlo())  # with an accumulator of its own
    step(1)  # after a reset
    # what train_step_hlo() lowers is what the step runs
    assert _digest(m._train_step_fn.lower(*m._step_args()[0]).as_text()) \
        == lowered
    # a new step function, here on another mesh where there is one to
    # take: one more program, and the accumulator goes over as it stands
    machine = Machine(devices=devices[:2]) if n_devices > 1 else None
    m.recompile(machine=machine)
    acc = m._metric_acc
    assert acc.committed and acc.sharding == m.machine.replicated()
    _with_batch(m, width=24)
    step(2)
    step(2)
    assert m.get_metrics().train_all == 3 * 8  # one before it, two after


# ---------------------------------------------------------------------------
# one pair of listeners
# ---------------------------------------------------------------------------

def _ours(listeners):
    return [f for f in listeners
            if getattr(f, "__module__", "").startswith("flexflow_tpu")
            or getattr(getattr(f, "__self__", None), "__module__",
                       "").startswith("flexflow_tpu")]


def test_one_duration_listener_and_one_event_listener(devices):
    models = [_staged(_mlp()) for _ in range(2)]
    stats = [compile_cache.CompileStats() for _ in range(3)]
    for m in models:
        m.train_iteration()
        m.sync()
    assert _ours(monitoring.get_event_duration_listeners()) \
        == [profiling._on_duration]
    assert _ours(monitoring.get_event_listeners()) == [profiling._on_event]
    assert all(s.snapshot()["compilations"] >= 0 for s in stats)


def test_compile_stats_are_differences_of_the_one_total(devices):
    first = compile_cache.CompileStats()
    jax.jit(lambda x: x * 9 + 4)(np.ones((3, 19))).block_until_ready()
    second = compile_cache.CompileStats()
    with profiling.phase(None, "t_stats"):  # whatever phase it fires in
        jax.jit(lambda x: x * 9 + 5)(np.ones((3, 19))).block_until_ready()
    a, b = first.snapshot(), second.snapshot()
    assert set(a) == {"compilations", "cache_hits", "cache_writes",
                      "compile_seconds"}
    assert (a["compilations"], b["compilations"]) == (2, 1)
    assert a["compile_seconds"] > b["compile_seconds"] > 0
    assert a["cache_hits"] + a["cache_writes"] <= a["compilations"]


# ---------------------------------------------------------------------------
# what is outside the program
# ---------------------------------------------------------------------------

@pytest.mark.skipif(not sys.platform.startswith("linux"), reason="/proc")
def test_the_age_of_a_process_with_a_known_start():
    started = time.time()
    child = subprocess.Popen([sys.executable, "-c",
                              "import time; time.sleep(60)"])
    try:
        time.sleep(0.3)
        age = profiling.process_age_s(f"/proc/{child.pid}/stat")
        assert age is not None and abs(age - (time.time() - started)) < 1.0
        own = profiling.process_age_s()
        assert own is not None and own > age
    finally:
        child.kill()
        child.wait(timeout=30)


def test_no_age_where_proc_is_not(tmp_path):
    assert profiling.process_age_s(str(tmp_path / "no_such_stat")) is None
    (tmp_path / "stat").write_text("12 (python) S 1 2")
    assert profiling.process_age_s(str(tmp_path / "stat")) is None


def _as_a_first_model(monkeypatch, stat):
    """The state of a process that has made no model yet."""
    monkeypatch.setattr(profiling, "_first_model_seen", False)
    monkeypatch.setattr(profiling, "_STAT", stat)
    for name in ("before_first_model_s", "graph_build_s"):
        monkeypatch.delitem(profiling._counters, name, raising=False)


@pytest.mark.skipif(not sys.platform.startswith("linux"), reason="/proc")
def test_the_first_model_writes_both_counters_once(devices, monkeypatch):
    _as_a_first_model(monkeypatch, "/proc/self/stat")
    own = profiling.process_age_s()
    cfg = ff.FFConfig(batch_size=8, compute_dtype="float32")
    m = ff.FFModel(cfg)
    time.sleep(0.05)  # the builder's Python
    first = profiling.counters()
    assert own <= first["before_first_model_s"] < own + 5
    assert "graph_build_s" not in first  # not until compile()
    second = ff.FFModel(cfg)
    assert second._graph_since is None
    t = m.dense(m.create_tensor((8, 8), nchw=False), 4, name="fc")
    m.softmax(t, name="sm")
    m.compile(ff.SGDOptimizer(m, lr=0.1),
              ff.LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
              [ff.MetricsType.ACCURACY])
    built = profiling.counters()
    assert 0.05 <= built["graph_build_s"] < 5
    assert built["before_first_model_s"] == first["before_first_model_s"]
    _mlp()
    assert profiling.counters()["graph_build_s"] == built["graph_build_s"]


def test_without_proc_the_age_is_absent_and_the_build_is_not(
        devices, monkeypatch, tmp_path):
    _as_a_first_model(monkeypatch, str(tmp_path / "no_proc"))
    _mlp()
    got = profiling.counters()
    assert "before_first_model_s" not in got
    assert got["graph_build_s"] > 0
