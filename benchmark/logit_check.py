#!/usr/bin/env python3
"""The logit comparison of a cell, at its published widths and timed sizes.

    python3 benchmark/logit_check.py --workload <cell> --seed <n> [--lower <what>]

Builds the cell's reported variant exactly as `run.py` does (the same
builder, optimizer, `init_layers(seed)` and staged batch), takes the
program's logits for that batch (`FFModel.logits_batch()`: the forward pass
of the train step's graph, in the compute dtype) and the plain reference's
(`reference.logits`, float32 at the highest matmul precision, a layer at a
time), and prints one JSON line: the norm-wise relative error over all
logits, `||program - reference|| / ||reference||`, the median, 90th and 99th
percentile over tokens of the same for each token's own logits, the loss
either side's logits give (what the harness's one comparison reads), and whether
the first is within the configuration's `logit_tolerance.rel` and the median
within its `logit_tolerance.token_p50`.  The first loss cannot tell
precisions apart (`loss_tolerance.why`); this can.

`--lower` runs the program in a precision below the one the configuration
states, to show that the limit refuses it: `router` computes the router's
product and softmax in bfloat16, `operands` cuts every projection's
operands to 8 bits (float8 e4m3).  Both patch this process only (the tests
apply the same two through `monkeypatch.setattr`).

It refuses to run without a TPU it knows the peaks of, as `run.py` does.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402


def lower_router(patch=setattr):
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.ops import moe

    def router_scores(x, router):
        bf16 = jnp.bfloat16
        return jax.nn.softmax(
            jnp.dot(x.astype(bf16), router.astype(bf16)).astype(bf16),
            axis=-1).astype(jnp.float32)
    patch(moe, "router_scores", router_scores)


def lower_operands(patch=setattr):
    import jax.numpy as jnp

    from flexflow_tpu.ops import linear

    def project(x, w):
        cut = lambda a: a.astype(jnp.float8_e4m3fn).astype(x.dtype)
        return jnp.dot(cut(x), cut(w.astype(x.dtype)),
                       preferred_element_type=jnp.float32).astype(x.dtype)
    patch(linear, "project", project)


LOWER = {"router": lower_router, "operands": lower_operands}


def relative_errors(got, want):
    """The norm-wise relative error of all logits, and the quantiles over
    tokens of each token's own (its row of logits): a token whose experts
    differ between the two sides is wrong by much and the others by the
    arithmetic's rounding, so the median reads the arithmetic and the
    whole reads both."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def rel(got, want):
        diff = got.astype(jnp.float32) - want
        rows = jnp.sqrt(jnp.sum(diff * diff, axis=-1)
                        / jnp.sum(want * want, axis=-1)).reshape(-1)
        return (jnp.sqrt(jnp.sum(diff * diff) / jnp.sum(want * want)),
                jnp.quantile(rows, jnp.array([0.5, 0.9, 0.99])))
    whole, rows = rel(got, want)
    return float(whole), [float(q) for q in rows]


def mean_nll(logits, labels):
    """The mean next-token cross-entropy of logits, in float32: what the
    harness's one comparison reads, for either side."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def nll(logits, labels):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, labels[..., None],
                                             axis=-1))
    return float(nll(logits, labels))


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--lower", choices=sorted(LOWER))
    args = p.parse_args(argv)

    cell = run.load_cell(ROOT, args.workload)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu" or dev.device_kind not in cell["peaks"]:
        raise run.Refused(f"no TPU of a known kind: platform={dev.platform} "
                          f"({dev.device_kind}); nothing was run")
    run.enable_compile_cache()
    if args.lower:
        LOWER[args.lower]()
    config, traffic = cell["config"], cell["traffic"]
    kwargs = config["builder_kwargs"]
    batch = traffic["batch_per_chip"] * cell["chips"]
    seed = args.seed % run.SEED_MODULUS
    ref = run.load_reference(cell["home"],
                             config.get("reference", cell["config_name"]))
    spec = next(v for v in traffic["variants"]
                if v["name"] == traffic["reported"])
    model = run.build_variant(cell, spec, batch, seed, run.Spans()).model
    inputs, labels = run.stage_batch(model, ref, jax.random.key(seed), batch,
                                     kwargs)
    got = model.logits_batch()
    want = ref.logits(run.params_of(model, dev), inputs, **kwargs)
    rel, (p50, p90, p99) = relative_errors(got, want)
    limits = config["logit_tolerance"]
    print(json.dumps({
        "workload": args.workload, "seed": args.seed, "lower": args.lower,
        "logits": list(got.shape), "relative_error": rel,
        "token_relative_error_p50": p50, "token_relative_error_p90": p90,
        "token_relative_error_p99": p99,
        "loss": {"program": mean_nll(got, labels),
                 "reference": mean_nll(want, labels)}, "limits": {
            k: limits[k] for k in ("rel", "token_p50")},
        "within": bool(rel <= limits["rel"] and p50 <= limits["token_p50"]),
        "device": {"platform": dev.platform, "kind": dev.device_kind}}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
