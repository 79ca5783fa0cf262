"""Chaos fault injection + step-level recovery (testing/chaos.py,
runtime/resilience.py).

Beyond the reference (strictly fail-stop, nothing checkpointed — SURVEY
§5.3/§5.4): every recovery path is exercised by a seeded, deterministic
fault and asserted bitwise — an injected NaN step leaves params
bit-identical and training converges anyway; a SIGTERM mid-epoch saves
and the rerun matches the uninterrupted run exactly; a failing
checkpoint write is retried and never leaves a partial file.
"""

import glob
import json
import os
import select
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import flexflow_tpu as ff
from flexflow_tpu.observability import events
from flexflow_tpu.runtime import resilience
from flexflow_tpu.runtime.elastic import elastic_train
from flexflow_tpu.runtime.resilience import (NonFiniteEscalationError,
                                             Preempted, with_ckpt_retries)
from flexflow_tpu.testing.chaos import (ChaosError, ChaosIOError,
                                        ChaosMonkey, from_env, parse_spec)


@pytest.fixture(autouse=True)
def _clean_env(monkeypatch):
    for k in ("FF_CHAOS", "FF_CHAOS_SEED", "FF_SKIP_NONFINITE",
              "FF_CKPT_RETRIES", "FF_CKPT_BACKOFF_S", "FF_TELEMETRY",
              "FF_TELEMETRY_FILE", "FF_HEALTH"):
        monkeypatch.delenv(k, raising=False)
    events.reset_active()
    yield
    events.reset_active()


def _build(n_samples=48, seed=9, n_devices=None):
    cfg = ff.FFConfig(batch_size=16)
    if n_devices is not None:
        cfg.parse_args(["-ll:tpu", str(n_devices)])
    m = ff.FFModel(cfg)
    inp = m.create_tensor((16, 8), nchw=False, name="input")
    t = m.dense(inp, 16, activation="relu", name="fc1")
    t = m.dense(t, 4, name="fc2")
    m.softmax(t, name="sm")
    m.compile(ff.AdamOptimizer(alpha=0.01),
              "sparse_categorical_crossentropy", ["accuracy"])
    m.init_layers(seed=seed)
    rng = np.random.default_rng(3)
    x = rng.standard_normal((n_samples, 8), dtype=np.float32)
    y = rng.integers(0, 4, size=(n_samples, 1), dtype=np.int32)
    dl = ff.DataLoader(m, {inp: x}, y, seed=5)
    return m, dl


# ---------------------------------------------------------------------------
# spec grammar
# ---------------------------------------------------------------------------

def test_parse_spec_grammar():
    exact, prob = parse_spec(
        "step:23=nan_loss;step:40=hang:2;ckpt_save:2=io_error;step:57=sigterm")
    assert exact[("step", 23)] == ("nan_loss", None)
    assert exact[("step", 40)] == ("hang", 2.0)
    assert exact[("ckpt_save", 2)] == ("io_error", None)
    assert exact[("step", 57)] == ("sigterm", None)
    assert prob == []

    exact, prob = parse_spec("data:p0.25=error")
    assert exact == {} and prob == [("data", 0.25, "error", None)]


@pytest.mark.parametrize("bad", [
    "nonsense", "step:=nan_loss", "badsite:1=nan_loss",
    "step:1=badfault", "step:px=error", "step:p1.5=error",
    "step:-1=error", "step:1=hang:soon", ";;",
])
def test_parse_spec_rejects_bad_entries(bad):
    with pytest.raises(ValueError):
        parse_spec(bad)


def test_exact_trigger_fires_once_and_prob_is_seeded():
    mk = ChaosMonkey("sync:2=error")
    assert mk.fire("sync") is None          # call 1
    with pytest.raises(ChaosError):
        mk.fire("sync")                     # call 2 fires
    assert mk.fire("sync") is None          # spent — never re-fires
    assert mk.fired == [("sync", 2, "error")]

    # probabilistic triggers are pure in (seed, site, index): two
    # monkeys with the same spec + seed fire on identical call indices
    def hit_indices():
        mk = ChaosMonkey("data:p0.2=error", seed=7)
        hits = []
        for i in range(200):
            try:
                mk.fire("data")
            except ChaosError:
                hits.append(i)
        return hits

    a, b = hit_indices(), hit_indices()
    assert a == b and 10 < len(a) < 80


def test_from_env_zero_cost_when_unset():
    assert from_env() is None
    m, _ = _build()
    assert m._chaos is None
    assert m._nonfinite_guard is None
    # no guard/health -> the metric vector carries only the base keys:
    # the train step compiles exactly as on an unchaosed build (no extra
    # entries, no select, no extra dispatches)
    assert m._metric_keys() == ["train_all", "train_correct", "cce_loss",
                                "sparse_cce_loss", "mse_loss", "rmse_loss",
                                "mae_loss", "loss", "steps"]


# ---------------------------------------------------------------------------
# NonFiniteGuard
# ---------------------------------------------------------------------------

def test_nan_step_is_skipped_bitwise_and_training_converges(
        monkeypatch, devices):
    monkeypatch.setenv("FF_CHAOS", "step:2=nan_loss")
    monkeypatch.setenv("FF_SKIP_NONFINITE", "5")
    m, dl = _build()
    losses = []
    for i in range(12):
        dl.next_batch(m)
        if i == 2:
            m.sync()
            pre = np.asarray(m.get_parameter("fc1", "kernel"))
        m.train_iteration()
        if i == 2:
            m.sync()
            post = np.asarray(m.get_parameter("fc1", "kernel"))
            # the poisoned step restored the PRE-step params bitwise
            assert (pre == post).all()
        m.get_metrics()
        if m.last_loss is not None:
            losses.append(m.last_loss)
    assert m._nonfinite_guard.total_skipped == 1
    assert m._chaos.fired == [("step", 2, "nan_loss")]
    assert all(np.isfinite(v) for v in losses)
    assert losses[-1] < losses[0]  # training converged anyway


def test_persistent_nan_escalates(monkeypatch, devices):
    monkeypatch.setenv("FF_CHAOS",
                       "step:1=nan_loss;step:2=nan_loss;step:3=nan_loss")
    monkeypatch.setenv("FF_SKIP_NONFINITE", "3")
    m, dl = _build()
    with pytest.raises(NonFiniteEscalationError, match="3 consecutive"):
        for _ in range(6):
            dl.next_batch(m)
            m.train_iteration()
            m.get_metrics()


def test_consec_run_survives_metric_reset(monkeypatch, devices):
    # the escalation counter is a run length across drains AND resets
    monkeypatch.setenv("FF_CHAOS",
                       "step:1=nan_loss;step:2=nan_loss;step:3=nan_loss")
    monkeypatch.setenv("FF_SKIP_NONFINITE", "3")
    m, dl = _build()
    with pytest.raises(NonFiniteEscalationError):
        for _ in range(6):
            dl.next_batch(m)
            m.train_iteration()
            m.get_metrics()
            m.reset_metrics()  # an epoch boundary between every step


@pytest.mark.parametrize("n_devices", [1, 4])
def test_a_run_carried_over_a_drain_or_a_reset_keeps_the_steps_program(
        monkeypatch, devices, n_devices):
    # the run length written into a fresh accumulator leaves it placed
    # as the step hands it back: no second program for the step
    monkeypatch.setenv("FF_CHAOS",
                       "step:1=nan_loss;step:2=nan_loss;step:4=nan_loss")
    monkeypatch.setenv("FF_SKIP_NONFINITE", "5")
    m, dl = _build(n_devices=n_devices)
    carried = []
    for i in range(7):
        dl.next_batch(m)
        m.train_iteration()
        assert m._train_step_fn._cache_size() == 1
        m.get_metrics()  # a drain after every step
        carried.append(m._nonfinite_guard.consec)
        acc = m._metric_acc
        assert acc.committed and acc.sharding == m.machine.replicated()
        if carried[-1]:
            m.reset_metrics()  # and a reset while a run is open
    assert m._nonfinite_guard.total_skipped == 3
    assert max(carried) == 2


# ---------------------------------------------------------------------------
# retrying atomic checkpoint I/O
# ---------------------------------------------------------------------------

def test_ckpt_io_error_retried_no_partial_file(tmp_path, monkeypatch,
                                               devices):
    monkeypatch.setenv("FF_CHAOS", "ckpt_save:1=io_error")
    monkeypatch.setenv("FF_CKPT_BACKOFF_S", "0.01")
    m, _ = _build()
    path = str(tmp_path / "w.npz")
    m.save(path)  # attempt 1 fails, retry succeeds
    assert os.path.exists(path)
    assert not glob.glob(str(tmp_path / "*.tmp-*"))
    assert ("ckpt_save", 1, "io_error") in m._chaos.fired
    # the checkpoint is loadable (not truncated)
    m.load(path)


def test_ckpt_retries_exhausted_propagates(monkeypatch):
    calls = []

    def always_fails():
        calls.append(1)
        raise ChaosIOError("disk on fire")

    with pytest.raises(ChaosIOError):
        with_ckpt_retries(always_fails, retries=2, base_delay=0.0,
                          sleep=lambda s: None)
    assert len(calls) == 3  # 1 + 2 retries


def test_atomic_npz_failed_write_leaves_nothing(tmp_path, devices):
    from flexflow_tpu.runtime import checkpoint as ck
    m, _ = _build()
    real = np.savez

    def boom(f, **kw):
        real(f, **kw)  # bytes hit the temp file...
        raise OSError("disk full")  # ...then the write "fails"

    np.savez = boom
    try:
        with pytest.raises(OSError):
            ck._save_npz(m, str(tmp_path / "x.npz"))
    finally:
        np.savez = real
    assert os.listdir(tmp_path) == []  # no final, no temp


# ---------------------------------------------------------------------------
# telemetry narration
# ---------------------------------------------------------------------------

def test_recovery_events_reach_trace_and_reports(tmp_path, monkeypatch,
                                                 devices):
    trace = str(tmp_path / "trace.jsonl")
    monkeypatch.setenv("FF_TELEMETRY", "1")
    monkeypatch.setenv("FF_TELEMETRY_FILE", trace)
    monkeypatch.setenv("FF_CHAOS", "step:2=nan_loss;ckpt_save:1=io_error")
    monkeypatch.setenv("FF_SKIP_NONFINITE", "5")
    monkeypatch.setenv("FF_CKPT_BACKOFF_S", "0.01")
    events.reset_active()
    m, dl = _build()
    for _ in range(4):
        dl.next_batch(m)
        m.train_iteration()
    m.get_metrics()
    m.save(str(tmp_path / "w.npz"))
    m._telemetry.flush()
    events.reset_active()

    names = [json.loads(l).get("name") for l in open(trace) if l.strip()]
    assert "fault_injected" in names
    assert "step_skipped" in names
    assert "ckpt_retry" in names

    from flexflow_tpu.tools import health_report, trace_report
    rep = trace_report.main([trace, "-o", str(tmp_path / "r.md")])
    assert "## Resilience" in rep
    assert "nan_loss" in rep and "ckpt_retry" in rep
    hrep = health_report.main([trace, "-o", str(tmp_path / "h.md")])
    assert "## Recovery" in hrep
    assert "non-finite steps skipped: 1" in hrep


# ---------------------------------------------------------------------------
# preemption (in-process signal + real subprocess kill)
# ---------------------------------------------------------------------------

def test_sigterm_preemption_saves_then_resume_is_bitwise(
        tmp_path, monkeypatch, devices):
    # uninterrupted baseline: 2 epochs (6 steps)
    mb, dlb = _build()
    elastic_train(mb, dlb, epochs=2,
                  checkpoint_dir=str(tmp_path / "base"))
    base = np.asarray(mb.get_parameter("fc1", "kernel"))

    # victim: chaos delivers a REAL SIGTERM during step 4's update; the
    # in-flight step completes, the loop saves at the next boundary and
    # exits cleanly via Preempted (a SystemExit with code 0)
    monkeypatch.setenv("FF_CHAOS", "step:4=sigterm")
    m, dl = _build()
    with pytest.raises(Preempted) as ei:
        elastic_train(m, dl, epochs=2, checkpoint_dir=str(tmp_path / "ck"))
    assert ei.value.code == 0
    assert ei.value.step == 5
    meta = resilience.read_resume_meta(str(tmp_path / "ck"))
    assert meta["step"] == 5 and meta["steps_per_epoch"] == 3

    # "process restart": fresh model + loader, chaos off
    monkeypatch.delenv("FF_CHAOS")
    m2, dl2 = _build()
    elastic_train(m2, dl2, epochs=2, checkpoint_dir=str(tmp_path / "ck"))
    got = np.asarray(m2.get_parameter("fc1", "kernel"))
    assert m2._step_count == 6
    assert (got == base).all()  # bitwise — not just allclose


_CHILD = """
import os, sys
sys.path.insert(0, {root!r})
import numpy as np
import flexflow_tpu as ff
from flexflow_tpu.runtime.elastic import elastic_train

cfg = ff.FFConfig(batch_size=16)
m = ff.FFModel(cfg)
inp = m.create_tensor((16, 8), nchw=False, name="input")
t = m.dense(inp, 16, activation="relu", name="fc1")
t = m.dense(t, 4, name="fc2")
m.softmax(t, name="sm")
m.compile(ff.AdamOptimizer(alpha=0.01), "sparse_categorical_crossentropy",
          ["accuracy"])
m.init_layers(seed=9)
rng = np.random.default_rng(3)
x = rng.standard_normal((48, 8), dtype=np.float32)
y = rng.integers(0, 4, size=(48, 1), dtype=np.int32)
dl = ff.DataLoader(m, {{inp: x}}, y, seed=5)


def first_epoch_done(epoch, _metrics):
    # one line once the first steps have run; the parent kills on it, so
    # the epochs below only have to outlast the parent
    if epoch == 0:
        print("STEPPED", flush=True)


elastic_train(m, dl, epochs=1_000_000, checkpoint_dir={ckpt!r},
              on_epoch=first_epoch_done)
"""


def _wait_for(proc, token: bytes, timeout: float) -> None:
    """Read the child's stdout until ``token``; fail after ``timeout`` s
    or when the child exits first."""
    fd = proc.stdout.fileno()
    seen = b""
    deadline = time.monotonic() + timeout
    while token not in seen:
        left = deadline - time.monotonic()
        assert left > 0 and select.select([fd], [], [], left)[0], \
            f"child printed {seen!r}, not {token!r}, in {timeout} s"
        chunk = os.read(fd, 4096)
        assert chunk, f"child exited (rc {proc.wait()}) before {token!r}"
        seen += chunk


def test_kill_term_subprocess_then_rerun_matches_uninterrupted(
        tmp_path, devices):
    """A real ``kill -TERM`` against a separate process mid-training:
    the child saves and exits 0; the rerun lands on the uninterrupted
    run's trajectory exactly (same global step => same params)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ckpt = str(tmp_path / "ck")
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=8"})
    for k in ("FF_CHAOS", "FF_TELEMETRY", "FF_HEALTH"):
        env.pop(k, None)
    code = _CHILD.format(root=root, ckpt=ckpt)
    proc = subprocess.Popen([sys.executable, "-c", code], env=env,
                            stdout=subprocess.PIPE)
    try:
        # the handler is installed and steps have run: kill mid-training
        _wait_for(proc, b"STEPPED", timeout=300)
        proc.send_signal(signal.SIGTERM)
        assert proc.wait(timeout=120) == 0  # clean exit after the save
    finally:
        proc.kill()
        proc.wait()

    meta = resilience.read_resume_meta(ckpt)
    assert meta is not None and meta["step"] > 0
    saved_step = int(meta["step"])

    # rerun up to a fixed target past the kill point, vs uninterrupted
    target_epochs = saved_step // 3 + 2
    m2, dl2 = _build()
    elastic_train(m2, dl2, epochs=target_epochs, checkpoint_dir=ckpt)
    mb, dlb = _build()
    elastic_train(mb, dlb, epochs=target_epochs,
                  checkpoint_dir=str(tmp_path / "base"))
    assert m2._step_count == mb._step_count
    got = np.asarray(m2.get_parameter("fc1", "kernel"))
    base = np.asarray(mb.get_parameter("fc1", "kernel"))
    assert (got == base).all()


# ---------------------------------------------------------------------------
# data / sync sites
# ---------------------------------------------------------------------------

def test_data_and_sync_sites_fire(monkeypatch, devices):
    monkeypatch.setenv("FF_CHAOS", "data:2=error;sync:1=error")
    m, dl = _build()
    dl.next_batch(m)          # data call 1: no fire
    with pytest.raises(ChaosError, match="data:2"):
        dl.next_batch(m)      # data call 2 fires
    with pytest.raises(ChaosError, match="sync:1"):
        m.sync()
