"""Chip smoke: the quickest proof that the system still starts on the TPU.

    python chip_smoke.py                      # on a machine with a TPU
    python chip_smoke.py --cpu-tiny           # tier-1: tiny sizes, CPU

One process drives the main path through the entry points a user calls,
at the full width of the models the repo ships, and checks what comes
out by the repo's own means:

  alexnet          full-width AlexNet (3x229x229), batch 256, bf16, one
                   chip, through the calls examples/alexnet.py makes:
                   loss finite and falling on one repeated synthetic
                   batch, parameters changed, parameters and batch
                   resident on the TPU, no compilation in the timed
                   window, the image stem computed space-to-depth
                   (Conv2D.impl_used) and no other convolution.  Prints
                   samples/s for the reader, not a claim.
  kernels          kernels/flash_attention.py against mha_reference on
                   the chip, forward and gradients, causal, bf16 and f32;
                   its line says which tiling ran at each shape, and
                   what the selective scan's kernels do with the Mamba
                   layers of Phi-4-mini-flash (the MXU's rows of lanes
                   a chunk for dB / dC among them).
  transformer      the bench transformer (4 layers x 512, 8 heads of 64,
                   seq 512, batch 16, bf16) for three train steps with
                   its attention in the compiled Pallas kernels (the
                   step's HLO carries the tpu_custom_call) and its
                   accuracy read from the logits (no instruction under
                   the final Softmax's scope; the line gives the device
                   time a step under ff.metrics and under that scope,
                   from a profile of three more steps; and which form
                   each embedding's table gradient took,
                   Embedding.grad_impl_used); then
                   serving.InferenceEngine over the same model answers
                   four requests of mixed prompt length and its tokens
                   are compared with FFModel.generate().
  fused_optimizer  two AlexNet steps with --fused-optimizer (a yes/no
                   for ROADMAP D3).
  multichip        with >= 4 devices: full-width AlexNet, global batch
                   256 on four chips under data parallelism, a hybrid
                   strategy and a searched one; shards on four distinct
                   devices with the shapes the strategy implies, float32
                   loss equal to the one-chip run's, bf16 loss falling.
                   Otherwise prints "multichip: skipped, N device(s)".

Every phase is fatal.  Without a TPU the script exits non-zero before it
builds anything and prints no result.  On success the last line of
stdout is one JSON object, {"ok": true, "device": {...}}, with the
device as JAX reports it.  --cpu-tiny runs every phase function at a
tiny size with the kernels interpreted, for the tests; every line it
prints then says platform=cpu and none of them is a result.
"""

import argparse
import gc
import importlib.metadata
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

FULL = dict(
    alexnet_batch=256, image=229, warmup=3, timed=12,
    layers=4, embed=512, heads=8, seq=512, batch=16, vocab=32000,
    flash_shapes=((16, 8, 512, 64), (4, 16, 1024, 64), (2, 16, 4096, 128)),
    flash_window=513, index_shape=(1, 2048, 8, 128),
    serve_seq=128, prompts=(5, 12, 33, 60), new_tokens=(8, 12, 16, 10),
    search_budget=2000)
TINY = dict(
    alexnet_batch=8, image=67, warmup=2, timed=3,
    layers=2, embed=64, heads=4, seq=64, batch=4, vocab=128,
    flash_shapes=((2, 2, 64, 16), (1, 2, 128, 32)),
    flash_window=9, index_shape=(1, 256, 2, 128),
    serve_seq=64, prompts=(3, 5, 9, 17), new_tokens=(4, 6, 8, 5),
    search_budget=200)

PHASES = ("alexnet", "kernels", "transformer", "fused_optimizer",
          "multichip")

# Largest error, as a share of the reference's largest magnitude, that
# bf16 operands (8 bits of mantissa) and an f32 accumulator explain.
KERNEL_TOL = 2e-2
# A served token that differs from generate()'s must be a near-tie in
# the reference distribution: relative probability gap below this.
TIE_TOL = 5e-2
MULTICHIP_RTOL = 1e-4

_prefix = ""


def say(msg):
    print(f"{_prefix}{msg}", flush=True)


def check(cond, msg):
    if not cond:
        raise SystemExit(f"{_prefix}chip_smoke: FAILED: {msg}")


def result(phase, **fields):
    say(f"phase {phase}: ok {json.dumps(fields, sort_keys=True)}")


# --------------------------------------------------------------------------
# alexnet: the trainer's main path
# --------------------------------------------------------------------------

def _alexnet(argv, sz, strategies=None):
    """Build + compile + init through the calls examples/alexnet.py
    makes; returns (model, data_loader)."""
    import flexflow_tpu as ff
    from flexflow_tpu.models.alexnet import build_alexnet

    cfg = ff.FFConfig()
    cfg.parse_args(argv)
    cfg.strategies.update(strategies or {})
    model = ff.FFModel(cfg)
    inp, _ = build_alexnet(model, cfg.batch_size, height=sz["image"],
                           width=sz["image"])
    model.compile(ff.SGDOptimizer(model, lr=0.001),
                  ff.LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
                  [ff.MetricsType.ACCURACY,
                   ff.MetricsType.SPARSE_CATEGORICAL_CROSSENTROPY])
    loader = ff.DataLoader.synthetic(model, inp, num_samples=cfg.batch_size)
    model.init_layers()
    loader.next_batch(model)
    return model, loader


def _step_loss(model):
    """One train step on the staged batch; its loss, read back."""
    model.reset_metrics()
    model.train_iteration()
    model.sync()
    model.get_metrics()
    return model.last_loss


def phase_alexnet(sz, dev, stats):
    import numpy as np

    b = sz["alexnet_batch"]
    t0 = time.perf_counter()
    model, _ = _alexnet(["-b", str(b), "-ll:tpu", "1", "--bf16"], sz)
    w0 = model.get_parameter("conv1", "kernel")
    loss_first = _step_loss(model)
    for _ in range(sz["warmup"] - 1):
        model.train_iteration()
    model.sync()
    compile_s = time.perf_counter() - t0

    before = stats.snapshot()
    t0 = time.perf_counter()
    for _ in range(sz["timed"]):
        model.train_iteration()
    model.sync()
    dt = time.perf_counter() - t0
    in_window = stats.snapshot()["compilations"] - before["compilations"]
    loss_last = _step_loss(model)

    check(math.isfinite(loss_first) and math.isfinite(loss_last),
          f"alexnet loss not finite: {loss_first} -> {loss_last}")
    check(loss_last < loss_first,
          f"alexnet loss did not fall: {loss_first} -> {loss_last}")
    check(not np.array_equal(w0, model.get_parameter("conv1", "kernel")),
          "alexnet conv1 kernel unchanged after training")
    check(in_window == 0,
          f"{in_window} compilation(s) inside the timed window")
    off = {k: sorted({d.platform for d in a.devices()})
           for k, a in model.placement().items()
           if {d.platform for d in a.devices()} != {dev.platform}}
    check(not off, f"arrays not on {dev.platform}: {off}")
    s2d = [op.name for op in model.ops
           if getattr(op, "impl_used", None)
           and op.impl_used[0] == "space_to_depth"]
    check(s2d == ["conv1"], f"convolutions computed space-to-depth: {s2d}, "
                            f"wanted the image stem alone")
    result(
        "alexnet", batch=b, image=sz["image"], dtype="bfloat16",
        conv_space_to_depth=s2d,
        loss_first=loss_first, loss_last=loss_last, timed_steps=sz["timed"],
        compilations_in_window=in_window,
        build_compile_warmup_s=round(compile_s, 1),
        samples_per_s=round(sz["timed"] * b / dt, 1),
        device_kind=dev.device_kind, devices=1)


# --------------------------------------------------------------------------
# kernels: flash attention against the reference
# --------------------------------------------------------------------------

def phase_kernels(sz, dev, stats):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from flexflow_tpu.kernels.flash_attention import (flash_attention,
                                                      mha_reference, tiling)

    interpret = dev.platform != "tpu"

    def graded(attention):
        """jitted (loss, out), (dq, dk, dv) of sum(attention(q,k,v) * w)"""
        def f(q, k, v, w):
            o = attention(q, k, v, causal=True)
            return jnp.sum(o.astype(jnp.float32) * w), o
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2),
                                          has_aux=True))

    flash = graded(lambda *a, **kw: flash_attention(*a, interpret=interpret,
                                                    **kw))
    ref = graded(mha_reference)

    def said(tiling_):
        # per kernel "block_q x block_k body/grid steps", and under a
        # window "pairs computed/band pairs"
        return {kernel: "{block_q}x{block_k} {body_steps}/{grid_steps}"
                .format(**t) + (" pairs {pairs_computed}/{band_pairs}"
                                .format(**t) if "band_pairs" in t else "")
                for kernel, t in tiling_.items()}

    worst, tiles = {}, {}
    for shape in sz["flash_shapes"]:
        # which tiling ran
        tiles["x".join(map(str, shape))] = said(tiling(
            shape[2], shape[2], shape[3], causal=True))
        for dtype in (jnp.bfloat16, jnp.float32):
            ks = jax.random.split(jax.random.key(shape[2]), 4)
            q, k, v = (jax.random.normal(kk, shape, jnp.float32).astype(dtype)
                       for kk in ks[:3])
            w = jax.random.normal(ks[3], shape, jnp.float32)
            tag = f"{'x'.join(map(str, shape))}/{jnp.dtype(dtype).name}"
            if not interpret:
                check("tpu_custom_call" in flash.lower(q, k, v, w).as_text(),
                      f"flash_attention {tag} did not lower to a "
                      f"tpu_custom_call")
            (_, o), grads = flash(q, k, v, w)
            # the oracle: f32 arithmetic, one batch row at a time (the
            # long shape's (S, S) scores are 1 GiB per row)
            with jax.default_matmul_precision("highest"):
                rows = [ref(q[i:i + 1], k[i:i + 1], v[i:i + 1], w[i:i + 1])
                        for i in range(shape[0])]
            want = [jnp.concatenate([r[0][1] for r in rows])] + [
                jnp.concatenate([r[1][j] for r in rows]) for j in range(3)]
            errs = {}
            for name, a, r in zip(("out", "dq", "dk", "dv"),
                                  (o,) + tuple(grads), want):
                a = np.asarray(a, np.float32)
                r = np.asarray(r, np.float32)
                check(np.isfinite(a).all(), f"flash {tag} {name} not finite")
                errs[name] = float(np.abs(a - r).max() / np.abs(r).max())
            worst[tag] = round(max(errs.values()), 5)
            check(worst[tag] <= KERNEL_TOL,
                  f"flash_attention {tag} off the reference: {errs}")
    # what the same kernels do with the last shape under a window and
    # under a selection (flash_win_*, flash_sel_*)
    longest = sz["flash_shapes"][-1]
    for tag, how in ((f"window{sz['flash_window']}",
                      dict(window=sz["flash_window"])),
                     ("selected", dict(selected=True))):
        tiles["x".join(map(str, longest)) + "/" + tag] = said(tiling(
            longest[2], longest[2], longest[3], causal=True, **how))
    # the index's scores and their gradient (dsa_index_fwd, _bwd)
    # against ops/dsa.py's blocks, and what they do with dots3's index
    from flexflow_tpu.kernels import dsa_index
    from flexflow_tpu.ops import dsa
    b, t, h, d = sz["index_shape"]
    ks = jax.random.split(jax.random.key(t), 4)
    q, k, w = (jax.random.normal(kk, shp, jnp.float32) for kk, shp in zip(
        ks, ((b, t, h, d), (b, t, d), (b, t, h))))
    g = jnp.tril(jax.random.normal(ks[3], (b, t, t), jnp.float32))

    def scored(impl):
        def f(q, k, w):
            scores = jnp.tril(dsa.index_scores(q, k, w, jnp.bfloat16, 256,
                                               1024, impl))
            return jnp.sum(scores * g), scores
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2),
                                          has_aux=True))
    (_, got), got_grads = scored(
        "pallas_interpret" if interpret else "pallas")(q, k, w)
    (_, want), want_grads = scored("xla")(q, k, w)
    tag = "index/" + "x".join(map(str, sz["index_shape"]))
    errs = {name: float(jnp.abs(a - r).max() / jnp.abs(r).max())
            for name, a, r in zip(("scores", "dq", "dk", "dw"),
                                  (got,) + got_grads, (want,) + want_grads)}
    worst[tag] = round(max(errs.values()), 5)
    check(errs["scores"] <= 1e-5 and worst[tag] <= KERNEL_TOL,
          f"dsa_index {tag} off ops/dsa.py's blocks: {errs}")
    for shape in ((t, h, d), (8192, 64, 128)):
        tiles["index/" + "x".join(map(str, shape))] = said(
            dsa_index.tiling(*shape))
    # the selective scan at phi4flash's Mamba layers: 8192 tokens, 5120
    # channels, a state of 16
    from flexflow_tpu.kernels import ssm_scan
    tiles["scan/8192x5120x16"] = (
        "{channels_per_block}x{chunk} {grid_steps} steps, lane sums "
        "{lane_sum_rows} rows a chunk".format(
            **ssm_scan.tiling(8192, 5120, 16)))
    result("kernels", compiled=not interpret, tolerance=KERNEL_TOL,
           max_normalized_error=worst, tiling=tiles)


# --------------------------------------------------------------------------
# transformer: train steps through the kernels, then the serving engine
# --------------------------------------------------------------------------

def _reference_probs(model, tokens):
    """(T, V) next-token probabilities after each prefix of one
    sequence, by the batch-of-one dense decode generate() runs."""
    import jax
    import jax.numpy as jnp

    tok_t, pos_t = model.resolve_decode_inputs()
    toks = jnp.asarray(tokens, jnp.int32)[None]

    @jax.jit
    def run(params, stats, toks):
        def body(caches, t):
            probs, caches = model.decode_step(params, stats, caches,
                                              toks[:, t], t, tok_t, pos_t)
            return caches, probs[0]

        caches = model.init_decode_caches(1, toks.shape[1])
        return jax.lax.scan(body, caches, jnp.arange(toks.shape[1]))[1]

    return run(model._decode_params(), model._stats, toks)


def _tail_of_the_step(model, classes, steps=3):
    """What the train step spends on the loss, on the metric vector and
    on the final Softmax: instructions under `ff.loss`, `ff.metrics` and
    the Softmax's forward scope in the loaded step programs (the most
    over them, where the process loaded several), and from a profile of
    `steps` steps, joined to the scope map as the benchmark joins it:
    device ms a step under each scope (every phase), and of the
    instructions under `ff.loss` those whose result is f32 and `classes`
    wide.  These are None where the trace holds no TPU's operations."""
    import glob
    import tempfile
    import types

    from benchmark import reduce
    from benchmark.readers import device_scope
    from flexflow_tpu.model import _op_scope
    from flexflow_tpu.runtime import profiling

    softmax, head = _op_scope(model.ops[-1]), _op_scope(model.ops[-2])
    with tempfile.TemporaryDirectory() as logdir:
        with profiling.trace(logdir):
            for _ in range(steps):
                model.train_iteration()
            model.sync()
        mine = [(text, m) for name, text, m in profiling.step_programs()
                if name == "jit_step"
                and any(e["scope"] == head for e in m.values())]
        check(mine, f"no loaded step program holds the scope {head}")
        path, = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        joined = device_scope.join(types.SimpleNamespace(
            trace=reduce.load(path), trace_window=None, trace_steps=steps,
            say=say))
    out = {}
    for key, name in (("loss", "ff.loss"), ("metrics", "ff.metrics"),
                      ("final_softmax", softmax)):
        out[f"{key}_instructions"] = max(
            sum(e["scope"] == name for e in m.values()) for _, m in mine)
        out[f"{key}_ms_per_step"] = None if joined is None else round(
            1e3 * sum(s for (sc, _, _), s in joined.items() if sc == name), 4)
    # a CPU's fusions say nothing of the chip's
    out["loss_classwide_f32_instructions"] = None if joined is None else max(
        _classwide_f32(text, m, "ff.loss", classes) for text, m in mine)
    return out


def _classwide_f32(text, scopes, scope, classes):
    """How many instructions of a step program under `scope` give an f32
    result `classes` wide.  `scopes` is the program's scope map, which
    holds the instructions the device runs on their own; in the HLO
    `text` each stands as `name = type opcode(...)`, the type a tuple for
    a fusion with several results."""
    import re

    results = re.findall(  # a tuple's end is the ")" before the opcode
        r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s+=\s+(\(.*?\)|\S+)\s+[\w\-]+\(",
        text, re.M)
    wide = re.compile(rf"\bf32\[(?:\d+,)*{classes}(?:,\d+)*\]")
    return sum(scopes.get(name, {}).get("scope") == scope
               and bool(wide.search(result)) for name, result in results)


def phase_transformer(sz, dev, stats):
    import numpy as np

    import flexflow_tpu as ff
    from flexflow_tpu.kernels.flash_attention import KERNELS
    from flexflow_tpu.models.transformer import (build_transformer,
                                                 synthetic_lm_batch)
    from flexflow_tpu.ops.attention import MultiHeadAttention
    from flexflow_tpu.serving import InferenceEngine

    on_tpu = dev.platform == "tpu"
    b, seq, vocab = sz["batch"], sz["seq"], sz["vocab"]
    cfg = ff.FFConfig()
    cfg.parse_args(["-b", str(b), "-ll:tpu", "1", "--bf16"])
    model = ff.FFModel(cfg)
    tok, pos, _ = build_transformer(
        model, b, seq_length=seq, num_layers=sz["layers"],
        embed_dim=sz["embed"], num_heads=sz["heads"], vocab_size=vocab)
    attn = [op for op in model.ops if isinstance(op, MultiHeadAttention)]
    if not on_tpu:
        for op in attn:  # asked for by name; never a default
            op.impl = "pallas_interpret"
    model.compile(ff.SGDOptimizer(model, lr=0.001),
                  ff.LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
                  [ff.MetricsType.ACCURACY])
    model.init_layers()
    toks, posa, labels = synthetic_lm_batch(b, seq, vocab)
    model.set_batch({tok: toks, pos: posa}, labels)

    want = "pallas" if on_tpu else "pallas_interpret"
    calls = model.train_step_hlo().count("tpu_custom_call")
    check(all(op.impl_used and op.impl_used[0] == want for op in attn),
          f"attention ran {[op.impl_used for op in attn]}, wanted {want}")
    if on_tpu:
        # each kernel's call is jitted on its own, so the traced module
        # holds a kernel once however many layers call it
        check(calls >= len(KERNELS),
              f"train step HLO has {calls} tpu_custom_call(s) for the "
              f"{len(KERNELS)} flash kernels of {len(attn)} attention ops")
    losses = [_step_loss(model) for _ in range(3)]
    check(all(math.isfinite(x) for x in losses),
          f"transformer loss not finite: {losses}")
    check(losses[-1] < losses[0], f"transformer loss not falling: {losses}")
    tail = _tail_of_the_step(model, vocab)
    check(tail["final_softmax_instructions"] == 0,
          f"accuracy alone was asked for, yet the train step runs the final "
          f"Softmax: {tail}")
    check(not tail["loss_classwide_f32_instructions"],
          f"the loss writes f32 as wide as the {vocab} classes: {tail}")
    result("transformer", layers=sz["layers"], embed=sz["embed"],
           heads=sz["heads"], seq=seq, batch=b, dtype="bfloat16",
           attention=want, tpu_custom_calls_in_step=calls, losses=losses,
           embedding_grad={op.name: op.grad_impl_used[0] for op in model.ops
                           if op._type == "Embedding"},
           **tail)

    # the second surface on the same graph: the serving engine
    rng = np.random.default_rng(7)
    reqs = [(rng.integers(0, vocab, size=p).astype(np.int32), n)
            for p, n in zip(sz["prompts"], sz["new_tokens"])]
    with InferenceEngine(model, max_batch=4, max_seq=sz["serve_seq"],
                         max_new_tokens=max(sz["new_tokens"])) as engine:
        handles = [engine.submit(p, n) for p, n in reqs]
        served = [h.result(timeout=600) for h in handles]
        estats = engine.stats()
    check(estats["completed"] == len(reqs) and estats["failed"] == 0,
          f"engine stats {estats}")
    equal, parted = 0, []
    for i, ((prompt, n), got) in enumerate(zip(reqs, served)):
        ref = model.generate(prompt[None], n)[0]
        check(got.shape == ref.shape and (got >= 0).all()
              and (got < vocab).all(), f"request {i}: bad tokens {got}")
        same = bool(np.array_equal(got, ref))
        equal += same
        if same and i:
            continue
        # where the two part (and for request 0 always, at token 0: that
        # checks this very diagnostic against generate()), read the
        # reference distribution after the common prefix
        j = 0 if same else int(np.argmax(got != ref))
        probs = np.asarray(_reference_probs(
            model, np.concatenate([prompt, ref[:j]])))[-1]
        top = float(probs.max())
        check(abs(float(probs[ref[j]]) - top) <= TIE_TOL * top,
              f"request {i}: generate() token {ref[j]} is not the argmax "
              f"of the batch-of-one decode")
        gap = (top - float(probs[got[j]])) / top
        check(gap <= TIE_TOL,
              f"request {i} token {j}: served {got[j]} vs generate() "
              f"{ref[j]}, reference probability gap {gap:.4f} > {TIE_TOL}")
        if not same:
            parted.append({"request": i, "token": j, "gap": round(gap, 5)})
    result(
        "serving", requests=len(reqs), prompt_lengths=list(sz["prompts"]),
        new_tokens=list(sz["new_tokens"]), paged=bool(estats["paged"]),
        tokens_equal_generate=f"{equal}/{len(reqs)}",
        parted_at=parted, tie_tolerance=TIE_TOL)


# --------------------------------------------------------------------------
# fused optimizer: does it compile (ROADMAP D3)
# --------------------------------------------------------------------------

def phase_fused_optimizer(sz, dev, stats):
    model, _ = _alexnet(["-b", str(sz["alexnet_batch"]), "-ll:tpu", "1",
                         "--bf16", "--fused-optimizer"], sz)
    losses = [_step_loss(model) for _ in range(2)]
    check(all(math.isfinite(x) for x in losses),
          f"fused optimizer loss not finite: {losses}")
    result("fused_optimizer", compiled=dev.platform == "tpu",
                  losses=losses)


# --------------------------------------------------------------------------
# multichip: per-operator parallelisation over four chips
# --------------------------------------------------------------------------

def _hybrid_strategy(n):
    """The dryrun_multichip strategy at full width: conv1 split sample x
    height (halo exchange), the dense layers sample x parameter (tensor-
    parallel psum), everything else data parallel (resharding between)."""
    import flexflow_tpu as ff

    half = n // 2
    s = {"conv1": ff.ParallelConfig(dims=(half, n // half, 1, 1))}
    for name in ("fc1", "fc2", "fc3"):
        s[name] = ff.ParallelConfig(dims=(half, n // half))
    return s


def _check_shards(model, n, label):
    """Every parameter sits on ``n`` distinct devices in shards of the
    shape its op's resolved strategy implies."""
    arrays = model.placement()
    for op in model.ops:
        for w in op.weights:
            a = arrays[f"{op.name}/{w.name}"]
            want = tuple(
                full // (op.pc.dims[pd]
                         if pd is not None and pd < len(op.pc.dims) else 1)
                for full, pd in zip(w.dims, w.partition_dims
                                    or (None,) * len(w.dims)))
            devs = {s.device for s in a.addressable_shards}
            shapes = {tuple(s.data.shape) for s in a.addressable_shards}
            check(len(devs) == n and shapes == {want},
                  f"{label}: {op.name}/{w.name} pc {op.pc.dims}: shards "
                  f"{sorted(shapes)} on {len(devs)} device(s), wanted "
                  f"{want} on {n}")


def phase_multichip(sz, dev, stats):
    import jax

    n = 4
    if len(jax.devices()) < n:
        say(f"multichip: skipped, {len(jax.devices())} device(s)")
        return
    coords = [getattr(d, "coords", None) for d in jax.devices()[:n]]
    say(f"multichip: jax.devices()[:4] ids {[d.id for d in jax.devices()[:n]]} "
        f"coords {coords} (Machine reshapes them in this order)")

    b = str(sz["alexnet_batch"])
    runs = {
        "dp": (["-ll:tpu", str(n)], None),
        "hybrid": (["-ll:tpu", str(n)], _hybrid_strategy(n)),
        "searched": (["-ll:tpu", str(n), "--budget",
                      str(sz["search_budget"]), "--search-engine",
                      "population", "--seed", "0"], None),
    }

    def five_steps(argv, strategies, label):
        model, _ = _alexnet(["-b", b] + argv, sz, strategies)
        if label != "one chip":
            _check_shards(model, n, label)
        losses = [_step_loss(model) for _ in range(5)]
        check(all(math.isfinite(x) for x in losses),
              f"{label}: loss not finite: {losses}")
        pcs = {op.name: list(op.pc.dims) for op in model.ops
               if op.pc.num_parts() > 1 and op.pc.dims[0] != n}
        return losses, pcs

    out = {}
    with jax.default_matmul_precision("highest"):
        ref, _ = five_steps(["-ll:tpu", "1"], None, "one chip")
        for label, (argv, strategies) in runs.items():
            losses, pcs = five_steps(argv, strategies, f"{label} f32")
            rel = abs(losses[-1] - ref[-1]) / abs(ref[-1])
            check(rel <= MULTICHIP_RTOL,
                  f"{label}: float32 loss after five steps {losses[-1]} vs "
                  f"one chip {ref[-1]} (rel {rel:.2e})")
            out[label] = {"f32_rel_diff": float(f"{rel:.2e}"),
                          "non_dp_ops": pcs}
    for label, (argv, strategies) in runs.items():
        losses, _ = five_steps(argv + ["--bf16"], strategies, f"{label} bf16")
        check(losses[-1] < losses[0], f"{label}: bf16 loss not falling: "
                                      f"{losses}")
        out[label]["bf16_losses"] = [losses[0], losses[-1]]
    result("multichip", devices=n, batch=int(b),
                  one_chip_f32_loss=ref[-1], **out)


# --------------------------------------------------------------------------

def main(argv=None):
    global _prefix
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--cpu-tiny", action="store_true",
                   help="tiny sizes on the CPU with the kernels interpreted "
                        "(the tier-1 test's mode); prints no result")
    p.add_argument("--phases", default=",".join(PHASES),
                   help="comma list of phases to run (default: all); a "
                        "subset prints no result either")
    args = p.parse_args(argv)
    wanted = [s for s in args.phases.split(",") if s]
    unknown = sorted(set(wanted) - set(PHASES))
    if unknown:
        p.error(f"unknown phase(s) {unknown}; known: {list(PHASES)}")

    import jax

    dev = jax.devices()[0]
    count = len(jax.devices())
    if args.cpu_tiny:
        _prefix = f"platform={dev.platform} "
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "absent"
    say(f"jax {jax.__version__} libtpu {libtpu} platform={dev.platform} "
        f"device_kind={dev.device_kind!r} devices={count}")
    if args.cpu_tiny:
        check(dev.platform == "cpu", f"--cpu-tiny needs JAX_PLATFORMS=cpu, "
                                     f"found platform={dev.platform}")
    else:
        check(dev.platform == "tpu",
              f"no TPU: JAX found platform={dev.platform} "
              f"({dev.device_kind}); nothing was run")

    from flexflow_tpu.utils import native
    from flexflow_tpu.utils.compile_cache import (CompileStats,
                                                  enable_compile_cache)

    stats = CompileStats()
    say(f"compile cache: {enable_compile_cache()}")
    say(f"native libraries: {json.dumps(native.status(load_all=True))}")

    sz = TINY if args.cpu_tiny else FULL
    t_all = time.perf_counter()
    for name in wanted:
        t0 = time.perf_counter()
        globals()[f"phase_{name}"](sz, dev, stats)
        gc.collect()  # drop the phase's model before the next allocates
        say(f"phase {name}: {time.perf_counter() - t0:.1f} s")
    s = stats.snapshot()
    say(f"compile: {s['compilations']} compilation(s), "
        f"{s['cache_hits']} persistent-cache hit(s), {s['cache_writes']} "
        f"write(s), {s['compile_seconds']:.1f} s compiling; "
        f"total {time.perf_counter() - t_all:.1f} s")
    if args.cpu_tiny or wanted != list(PHASES):
        say(f"done: {wanted} passed; not a result")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
