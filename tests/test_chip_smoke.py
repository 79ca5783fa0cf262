"""The device boundary, checked on the CPU: chip_smoke.py refuses to run
without a TPU, its tiny mode runs every phase function, and the rules it
rests on — one compile-cache directory, no
silent device-count clamp, one sourced peaks table, explicit kernel
interpretation — hold."""

import json
import os
import re
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flexflow_tpu as ff
from flexflow_tpu.kernels.flash_attention import (_block_sizes,
                                                  flash_attention,
                                                  mha_reference,
                                                  unsupported_reason)
from flexflow_tpu.utils import compile_cache, native

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, devices=8, env=None, cwd=ROOT, timeout=600):
    e = dict(os.environ, JAX_PLATFORMS="cpu",
             XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    e.update(env or {})
    return subprocess.run([sys.executable] + args, cwd=cwd, env=e,
                          capture_output=True, text=True, timeout=timeout)


def _json_lines(text):
    out = []
    for line in text.splitlines():
        try:
            out.append(json.loads(line))
        except ValueError:
            pass
    return out


# ---------------------------------------------------------------------------
# chip_smoke.py
# ---------------------------------------------------------------------------

def test_smoke_without_tpu_fails_and_names_the_platform():
    r = _run([os.path.join(ROOT, "chip_smoke.py")])
    assert r.returncode != 0
    assert "no TPU" in r.stderr and "platform=cpu" in r.stderr
    assert "platform=cpu" in r.stdout.splitlines()[0]   # device header
    assert "phase" not in r.stdout                      # built nothing
    assert not _json_lines(r.stdout)                    # and no result


def test_smoke_tiny_mode_runs_every_phase():
    r = _run([os.path.join(ROOT, "chip_smoke.py"), "--cpu-tiny"])
    assert r.returncode == 0, r.stderr[-2000:]
    lines = r.stdout.splitlines()
    for phase in ("alexnet", "kernels", "transformer", "serving",
                  "fused_optimizer", "multichip"):
        assert any(ln.startswith(f"platform=cpu phase {phase}: ok ")
                   for ln in lines), (phase, r.stdout[-2000:])
    # the alexnet line names the convolutions computed space-to-depth
    line = next(ln for ln in lines if " phase alexnet: ok " in ln)
    assert json.loads(line.split(" ok ", 1)[1])["conv_space_to_depth"] == \
        ["conv1"]
    # the kernels line says, under the window, the pairs the running tiles
    # compute against the band's
    line = next(ln for ln in lines if " phase kernels: ok " in ln)
    tiles = json.loads(line.split(" ok ", 1)[1])["tiling"]
    assert all(" pairs " in t for t in tiles["1x2x128x32/window9"].values())
    assert not any(" pairs " in t for t in tiles["1x2x128x32"].values())
    # the transformer's line says which tensor the accuracy read: no
    # instruction under the final Softmax's scope, and the two device
    # times a step, which a CPU has not
    line = next(ln for ln in lines if " phase transformer: ok " in ln)
    said = json.loads(line.split(" ok ", 1)[1])
    assert said["final_softmax_instructions"] == 0
    assert said["metrics_instructions"] > 0
    assert said["final_softmax_ms_per_step"] is None
    assert said["metrics_ms_per_step"] is None
    # and what the loss costs: counted here, timed and judged on the chip
    assert said["loss_instructions"] > 0
    assert said["loss_ms_per_step"] is None
    assert said["loss_classwide_f32_instructions"] is None
    # and the form of each embedding's table gradient (rows 64 wide)
    assert said["embedding_grad"] == {"tok_embed": "scatter_add",
                                      "pos_embed": "scatter_add"}
    # every line the script prints says where it ran (the package's own
    # notices start "flexflow_tpu:"), and none of them is a result line
    assert all("platform=cpu" in ln for ln in lines
               if not ln.startswith("flexflow_tpu:"))
    assert not _json_lines(r.stdout)
    hdr = lines[0]
    assert re.search(r"jax \S+ libtpu \S+ platform=cpu "
                     r"device_kind='cpu' devices=8", hdr), hdr


def test_smoke_counts_the_f32_the_loss_writes_as_wide_as_the_classes():
    """On the parent's GPT-2 step the count was 2 (the relayouted copy
    and `log_softmax` written out); a row's statistics, bf16 logits and
    an instruction of another scope do not count."""
    import chip_smoke as smoke
    text = """HloModule jit_step
ENTRY %main (p: bf16[4,1024,50257]) -> f32[] {
  %p = bf16[4,1024,50257]{1,2,0:T(8,128)(2,1)} parameter(0)
  %copy.3047 = f32[4,1024,50257]{2,1,0:T(8,128)} copy(%p), metadata={op_name="jit(step)/jvp(ff.loss)/convert_element_type"}
  %subtract_subtract_fusion = f32[4096,50257]{1,0} fusion(%copy.3047), kind=kLoop, calls=%f, metadata={op_name="jit(step)/jvp(ff.loss)/sub"}
  %stats = (f32[4,1024]{1,0:T(4,128)S(1)}, f32[4,1024]{1,0:T(4,128)S(1)}) fusion(%p), kind=kLoop, calls=%g, metadata={op_name="jit(step)/jvp(ff.loss)/reduce_sum"}
  %pair = (f32[4,1024]{1,0:T(4,128)S(1)}, f32[4,1024,50257]{2,1,0:T(8,128)}) fusion(%p), kind=kLoop, calls=%g, metadata={op_name="jit(step)/jvp(ff.loss)/exp"}
  %probs = f32[4,1024,50257]{2,1,0} fusion(%p), kind=kLoop, calls=%h, metadata={op_name="jit(step)/ff.metrics/exp"}
  ROOT %loss = f32[] reduce(%stats), metadata={op_name="jit(step)/jvp(ff.loss)/reduce_sum"}
}
"""
    from flexflow_tpu.runtime import profiling

    scopes = profiling.parse_hlo_scopes(text)
    assert scopes["stats"]["scope"] == "ff.loss"
    assert smoke._classwide_f32(text, scopes, "ff.loss", 50257) == 3
    assert smoke._classwide_f32(text, scopes, "ff.metrics", 50257) == 1
    assert smoke._classwide_f32(text, scopes, "ff.loss", 257) == 0


def test_smoke_prints_the_multichip_skip():
    r = _run([os.path.join(ROOT, "chip_smoke.py"), "--cpu-tiny",
              "--phases", "multichip"], devices=1)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "multichip: skipped, 1 device(s)" in r.stdout


def test_smoke_alone_in_a_directory_fails(tmp_path):
    # the script without the program must not pass (the TPU check comes
    # first on a real run; --cpu-tiny gets past it here)
    import shutil

    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    r = _run([str(tmp_path / "chip_smoke.py"), "--cpu-tiny"],
             env={"PYTHONPATH": ""}, cwd=str(tmp_path))
    assert r.returncode != 0 and "No module named 'flexflow_tpu'" in r.stderr
    assert "phase" not in r.stdout and not _json_lines(r.stdout)


def test_smoke_rejects_an_unknown_phase():
    r = _run([os.path.join(ROOT, "chip_smoke.py"), "--cpu-tiny",
              "--phases", "alexnet,warp"])
    assert r.returncode != 0 and "unknown phase" in r.stderr


# ---------------------------------------------------------------------------
# the compile cache: one directory, placed from outside
# ---------------------------------------------------------------------------

# the option's name is built from pieces, here and below, so that this
# file is not itself a place that names it
_OPTION = "jax_compilation_" + "cache_dir"
_CACHE_PROBE = ("from flexflow_tpu.utils.compile_cache import "
                "enable_compile_cache as e; import jax; "
                f"print(e()); print(getattr(jax.config, '{_OPTION}'))")


def test_cache_helper_leaves_the_environments_directory_alone(tmp_path):
    want = str(tmp_path / "elsewhere")
    r = _run(["-c", _CACHE_PROBE], env={"JAX_COMPILATION_CACHE_DIR": want})
    assert r.returncode == 0, r.stderr[-500:]
    assert r.stdout.split() == [want, want]   # JAX read it; nothing was set
    assert not os.path.exists(want)           # and nothing was created


def test_cache_helper_defaults_to_the_checkout_from_any_process(tmp_path):
    want = os.path.join(ROOT, ".jax_cache")
    env = {"JAX_COMPILATION_CACHE_DIR": "", "PYTHONPATH": ROOT}
    outs = [_run(["-c", _CACHE_PROBE], env=env, cwd=cwd).stdout.split()
            for cwd in (ROOT, str(tmp_path))]
    assert outs == [[want, want], [want, want]]


def test_only_the_helper_names_the_cache_option():
    hits = []
    for top, dirs, files in os.walk(ROOT):
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d != "chiprun_out"]
        for f in files:
            if f.endswith(".py"):
                p = os.path.join(top, f)
                with open(p, encoding="utf-8") as fh:
                    if _OPTION in fh.read():
                        hits.append(os.path.relpath(p, ROOT))
    assert hits == ["flexflow_tpu/utils/compile_cache.py"]


def test_compile_stats_count_a_window():
    stats = compile_cache.CompileStats()

    @jax.jit
    def f(x):
        return x * 3 + 1

    x = jnp.ones((7, 3))
    a = stats.snapshot()
    f(x).block_until_ready()
    b = stats.snapshot()
    f(x).block_until_ready()                  # a steady call: no compile
    c = stats.snapshot()
    assert b["compilations"] == a["compilations"] + 1
    assert c["compilations"] == b["compilations"]


# ---------------------------------------------------------------------------
# devices: no guess, no clamp, one peaks table
# ---------------------------------------------------------------------------

def _mlp(cfg):
    m = ff.FFModel(cfg)
    t = m.dense(m.create_tensor((cfg.batch_size, 8), nchw=False), 4,
                name="fc")
    m.softmax(t, name="sm")
    return m


def test_more_devices_than_the_machine_has_raises(devices):
    cfg = ff.FFConfig(batch_size=16)
    cfg.parse_args(["-ll:tpu", "16"])
    m = _mlp(cfg)
    with pytest.raises(ValueError, match=r"16 device\(s\) requested.*has 8"):
        m.compile(ff.SGDOptimizer(m, lr=0.1),
                  ff.LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
                  [ff.MetricsType.ACCURACY])


def test_a_backend_that_fails_to_start_is_not_one_device():
    r = _run(["-c", "import flexflow_tpu as ff; ff.FFConfig()"],
             env={"JAX_PLATFORMS": "no_such_platform", "PYTHONPATH": ROOT})
    assert r.returncode != 0 and "no_such_platform" in r.stderr


def test_peaks_table_is_keyed_by_device_kind():
    from flexflow_tpu.simulator.machine import (DEVICE_PEAKS,
                                                TPUMachineModel,
                                                device_peak_flops)

    assert device_peak_flops("TPU v5 lite") == 197e12
    assert TPUMachineModel().peak_flops == \
        DEVICE_PEAKS["TPU v5 lite"]["bf16_flops"]
    for kind in ("TPU_v5e", "cpu", "TPU v9"):
        with pytest.raises(ValueError, match="no published peaks"):
            device_peak_flops(kind)


def test_sync_placement_and_step_hlo(devices):
    cfg = ff.FFConfig(batch_size=16)
    m = _mlp(cfg)
    m.compile(ff.SGDOptimizer(m, lr=0.1),
              ff.LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
              [ff.MetricsType.ACCURACY])
    m.init_layers(seed=0)
    m.sync()                                  # nothing dispatched yet: fine
    rng = np.random.default_rng(0)
    m.set_batch({m.input_tensors[0]: rng.standard_normal((16, 8),
                                                         np.float32)},
                rng.integers(0, 4, (16, 1), dtype=np.int32))
    hlo = m.train_step_hlo()
    assert "stablehlo" in hlo and "tpu_custom_call" not in hlo
    m.train_iteration()
    m.sync()
    where = m.placement()
    assert {"fc/kernel", "fc/bias", "batch/label"} <= set(where)
    assert all(len(a.sharding.device_set) == 8 for a in where.values())
    assert all(d.platform == "cpu" for a in where.values()
               for d in a.devices())


# ---------------------------------------------------------------------------
# kernels: interpretation is asked for by name
# ---------------------------------------------------------------------------

def _qkvw(shape, dtype, seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)
    q, k, v = (jax.random.normal(kk, shape, jnp.float32).astype(dtype)
               for kk in ks[:3])
    return q, k, v, jax.random.normal(ks[3], shape, jnp.float32)


@pytest.mark.parametrize("shape,dtype,tol", [
    ((2, 2, 64, 16), jnp.float32, 1e-5),
    ((2, 2, 64, 16), jnp.bfloat16, 2e-2),
    ((1, 2, 100, 16), jnp.float32, 1e-5),    # block = the whole sequence
    ((1, 1, 1040, 8), jnp.float32, 1e-5),    # 1040 = 8 * 130: blocks of 520
])
def test_flash_kernel_matches_reference_interpreted(shape, dtype, tol):
    q, k, v, w = _qkvw(shape, dtype)

    def graded(attention):
        def f(q, k, v):
            o = attention(q, k, v, causal=True)
            return jnp.sum(o.astype(jnp.float32) * w), o
        return jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)

    (_, o), g = graded(lambda *a, **kw: flash_attention(
        *a, interpret=True, **kw))(q, k, v)
    (_, o_ref), g_ref = graded(mha_reference)(q, k, v)
    for a, r in zip((o,) + tuple(g), (o_ref,) + tuple(g_ref)):
        a, r = np.asarray(a, np.float32), np.asarray(r, np.float32)
        assert np.abs(a - r).max() <= tol * np.abs(r).max()


def test_flash_kernel_never_interprets_by_default():
    q, k, v, _ = _qkvw((1, 1, 16, 8), jnp.float32)
    with pytest.raises(ValueError, match="[Ii]nterpret"):
        flash_attention(q, k, v)              # compiled mode, on a CPU


def test_untileable_sequence_is_rejected_in_python():
    assert _block_sizes(512, 4096, 64) == (512, 1024)
    assert _block_sizes(100, 100, 64) == (100, 100)
    assert _block_sizes(1000, 1000, 64) == (1000, 1000)
    assert _block_sizes(1040, 1040, 64) == (520, 520)
    assert _block_sizes(4096, 4096, 256) == (512, 512)
    for seq in (1009, 1018):                  # prime; 2 * 509
        with pytest.raises(ValueError, match=f"sequence length {seq}"):
            _block_sizes(seq, seq, 64)
        assert str(seq) in unsupported_reason(seq, 512)
    q, k, v, _ = _qkvw((1, 1, 1018, 8), jnp.float32)
    with pytest.raises(ValueError, match="1018"):
        flash_attention(q, k, v, interpret=True)


def _attention_model(seq=16):
    cfg = ff.FFConfig(batch_size=8)
    m = ff.FFModel(cfg)
    x = m.create_tensor((8, seq, 32), nchw=False, name="x")
    h = m.multihead_attention(x, num_heads=4, causal=True, name="attn")
    m.softmax(m.dense(h, 4, name="head"), name="sm")
    m.compile(ff.SGDOptimizer(m, lr=0.1),
              ff.LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
              [ff.MetricsType.ACCURACY])
    m.init_layers(seed=3)
    rng = np.random.default_rng(1)
    m.set_batch({x: rng.standard_normal((8, seq, 32), np.float32)},
                rng.integers(0, 4, (8, seq), dtype=np.int32))
    return m, next(op for op in m.ops if op.name == "attn")


def test_attention_records_the_path_it_took(devices):
    m, op = _attention_model()
    xla = m.predict_batch()
    assert op.impl_used == ("xla", "platform is cpu")
    m2, op2 = _attention_model()
    op2.impl = "pallas_interpret"
    np.testing.assert_allclose(m2.predict_batch(), xla, atol=1e-5)
    assert op2.impl_used == ("pallas_interpret", "set on the op")
    m3, op3 = _attention_model()
    op3.impl = "fastest"
    with pytest.raises(ValueError, match="unknown attention impl"):
        m3.predict_batch()


def test_attention_on_a_tpu_leaves_the_kernel_visibly(devices):
    _, op = _attention_model()
    tpu = types.SimpleNamespace(platform="tpu")
    op.model = types.SimpleNamespace(
        machine=types.SimpleNamespace(devices=[tpu]))
    assert op._pick_impl(512, 512) == ("pallas", "platform is tpu")
    with pytest.warns(UserWarning, match="1018.*using XLA attention"):
        impl, why = op._pick_impl(1018, 1018)
    assert impl == "xla" and "1018" in why


# ---------------------------------------------------------------------------
# native libraries: how each was obtained, judged by content
# ---------------------------------------------------------------------------

def test_native_status_says_how_each_library_was_obtained():
    st = native.status(load_all=True)
    assert set(st) == set(native.LIBRARIES)
    assert all(how in ("prebuilt", "built in this run") for how in
               st.values()), st


def test_native_staleness_is_by_source_digest_not_mtime(tmp_path):
    so, src = tmp_path / "libffx.so", tmp_path / "ffx.cpp"
    so.write_bytes(b"\x7fELF")
    src.write_text("int f() { return 1; }")
    assert native._stale(str(so))             # no digest recorded: rebuild
    (tmp_path / "libffx.so.src").write_text(native._source_digest(str(so)))
    assert not native._stale(str(so))
    os.utime(src, None)                       # newer mtime, same content
    assert not native._stale(str(so))
    src.write_text("int f() { return 2; }")
    os.utime(src, (0, 0))                     # older mtime, new content
    assert native._stale(str(so))


def test_calibrate_fit_only_touches_no_backend(tmp_path):
    # the supervising parent runs this path while its worker may hold
    # the chip: it must not initialise JAX
    r = _run(["-m", "flexflow_tpu.tools.calibrate", "--fit-only",
              "--out", str(tmp_path / "m.json"),
              "--fit-out", str(tmp_path / "f.json"), "--devices", "2",
              "--alexnet-batch", "64", "--bench-batch", "16",
              "--models", "alexnet", "--no-inception", "--quiet"],
             env={"JAX_PLATFORMS": "no_such_platform",
                  "FF_PERF_LEDGER": str(tmp_path / "log.jsonl"),
                  "FF_REPORT_KEYS_PATH": str(tmp_path / "keys.json")})
    assert r.returncode == 0, r.stderr[-1000:]
    assert "refitting from the cached TPU entries" in r.stdout
