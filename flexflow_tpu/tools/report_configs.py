"""Single source of truth for the benchmark/report configs.

The calibration (tools/calibrate.py) and the SOAP reports
(tools/soap_report.py) MUST price and measure the SAME global batch per
model, or the reports' measured provenance silently stays at zero —
cache keys encode sub-tensor shapes, so a batch mismatch means no
measured entry ever matches a priced op.  Both tools default from this
table.

Reference anchors: AlexNet global batch 64 is the reference default
(src/runtime/model.cc:1238, BASELINE.json config #1); DLRM/NMT use the
reports' historical 1024 (64/chip x 16).
"""

# global batch per model for the SOAP-vs-DP comparison (alexnet/dlrm/
# nmt at 16 chips; resnet at 64 chips — BASELINE.json config #5's
# "ResNet-50 with simulator-searched strategy on v5e-64 multi-host").
# resnet and inception (8 chips, the reference's bs-256 config) are
# SIMULATION-ONLY at report scale: calibrate's default job space does
# not enumerate their multi-device sub-shapes, so those reports are
# always priced by the fitted roofline (each report's provenance line
# states this).
REPORT_GLOBAL_BATCH = {
    "alexnet": 64,
    "dlrm": 1024,
    "nmt": 1024,
    "resnet": 2048,
    "inception": 256,
}

# machine size each model's SOAP report simulates (alexnet/dlrm/nmt at
# the 16-chip BASELINE configs; resnet config #5 at v5e-64; inception
# config #2's shape at 8 chips).  calibrate uses this to synthesize
# targeted jobs for the report shapes of models whose full candidate
# space it does not enumerate.
REPORT_DEVICES = {
    "alexnet": 16,
    "dlrm": 16,
    "nmt": 16,
    "resnet": 64,
    "inception": 8,
}

# single-chip AlexNet batch of the simulated-vs-measured agreement check
BENCH_SINGLE_CHIP_BATCH = 256

# Compute dtype the committed reports (and their measured-cache keys /
# priority hints) are priced in — part of soap_report's canonical-scale
# guard: a float32 run must not clobber the bfloat16 hint keys.
REPORT_COMPUTE_DTYPE = "bfloat16"

# A roofline fit from fewer points / op families than this extrapolates
# beyond its basis; calibrate warns and the reports disclose it.
THIN_FIT_POINTS = 16
THIN_FIT_OP_TYPES = 3

# doctor's "perf" section reports measured-cache coverage against this
# many TPU entries (the default ~654-job space majority-measured);
# shrink alongside --models if the job space is narrowed.
CALIBRATION_TARGET_ENTRIES = 350

def report_keys_path():
    """The ONE resolution of the calibration-priority hint file
    (written by soap_report, consumed by calibrate.build_job_list).
    FF_REPORT_KEYS_PATH diverts it — tests set that to a scratch path
    so small-config runs can never overwrite the committed hints."""
    import os

    from ..simulator.machine import CALIBRATION_PATH

    return os.environ.get(
        "FF_REPORT_KEYS_PATH",
        os.path.join(os.path.dirname(CALIBRATION_PATH),
                     "report_keys.json"))


# Annealing budget per model for the SOAP reports.  The per-iteration
# cost differs by orders of magnitude across models (alexnet's space
# anneals natively in seconds; the larger graphs pay more per step), so
# one global budget either under-converges the cheap searches or makes
# the expensive ones take an hour.  Restarts (independent seeds, best
# kept) apply on top — basin variance at fixed budget measured ~4.4 to
# 5.2x on alexnet@16.  Budgets sit at each model's measured
# convergence knee (4-restart best, fitted machine): alexnet 9.82x at
# 40k -> 10.67x at 160k, flat to 640k; dlrm 6.97x at 4k -> 8.07x at
# 64k, flat to 256k; nmt 2.99x at 4k -> 3.69x at 64k, flat to 320k
# (native engine, multi-output support); resnet@64 / inception@8 stay
# 1.00x (DP-optimal) even at 64k, so they keep the cheap default.
SEARCH_BUDGET = {"alexnet": 160000, "dlrm": 64000, "nmt": 64000}
SEARCH_BUDGET_DEFAULT = 4000
SEARCH_RESTARTS = 4
