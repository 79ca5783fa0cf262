"""Configuration and parallelization-config types.

TPU-native re-design of the reference FlexFlow configuration layer
(reference: include/config.h:26-115, src/runtime/model.cc:1274-1342).

Two levels of configuration, mirroring the reference:
  * ``FFConfig``  — run-level flags (epochs, batch size, lr, search budget,
    strategy file paths, device counts).  CLI flags keep the reference
    spellings (``-e``, ``-b``, ``--lr``, ``--budget`` ...) and add
    ``-ll:tpu N`` (accepted alias: ``-ll:gpu``) for the per-host device count.
  * ``ParallelConfig`` — per-operator SOAP partition description
    (reference: include/config.h:42-51): a device type, a per-tensor-dim
    partition degree vector, and the flat list of device ids that the
    op's task grid maps onto.

On TPU the ``device_ids`` do not drive placement directly (XLA GSPMD places
shards by mesh coordinates); they are preserved for strategy-file round
tripping and for the execution simulator's machine model.
"""

from __future__ import annotations

import dataclasses
import enum
import os
from typing import Dict, List, Optional, Sequence, Tuple

MAX_DIM = 4
MAX_NUM_WORKERS = 1024

# Default MCMC budget for offline/auto search entry points.  Sized for
# the delta (incremental) simulator in simulator/delta.py, which re-costs
# a proposal ~20x cheaper than the full task-graph rebuild the old
# 1000-2000 defaults were calibrated against — more budget at lower cost
# than before (set FF_SIM_DELTA=0 to get the old per-proposal price).
DEFAULT_SEARCH_BUDGET = 8000


class DeviceType(enum.Enum):
    """Device kind an op is placed on.

    The reference uses GPU/CPU (include/config.h:43-46); the TPU build maps
    the accelerator type to TPU and keeps CPU for host-resident ops
    (e.g. DLRM's zero-copy embedding tables).  Wire value 0 in strategy
    files means "the accelerator".
    """

    TPU = 0
    CPU = 1

    # Alias used when importing reference-era strategy files.
    GPU = 0


@dataclasses.dataclass(frozen=True)
class ParallelConfig:
    """Per-op SOAP partition config (reference: include/config.h:42-51).

    ``dims`` holds the partition degree for each dimension of the op's
    *output* tensor, in the tensor's natural dim order (batch first; image
    tensors are NHWC in this framework — the TPU-native layout).  The
    product of ``dims`` is the number of parts; ``device_ids`` lists the
    devices the parts map onto, length ``num_parts`` (may be empty, in
    which case parts map onto devices ``0..num_parts-1``).
    """

    device_type: DeviceType = DeviceType.TPU
    dims: Tuple[int, ...] = (1,)
    device_ids: Tuple[int, ...] = ()
    # Per-tensor memory placement (reference: Op.memory_types, strategy.proto
    # FBM=device HBM, ZCM=host pinned).  "hbm"/"host" here; host entries map
    # to JAX host-offload for CPU-placed embeddings (DLRM).
    memory_types: Tuple[str, ...] = ()

    def __post_init__(self):
        if len(self.dims) == 0 or len(self.dims) > MAX_DIM:
            raise ValueError(f"ParallelConfig dims must have 1..{MAX_DIM} entries, got {self.dims}")
        if any(d < 1 for d in self.dims):
            raise ValueError(f"partition degrees must be >= 1, got {self.dims}")

    @classmethod
    def host_rowsparse(cls, ndims: int = 2) -> "ParallelConfig":
        """Host placement for an embedding table (reference: the hetero
        DLRM strategies' CPU + ZC-memory placement,
        dlrm_strategy_hetero.cc:28-35) — the runtime's row-sparse
        host-resident path.  ONE definition shared by the strategy
        generators, both search engines, and the SOAP reports.

        ``ndims``: rank of the embedding's OUTPUT (2 for SUM/AVG bags,
        3 for aggr=NONE sequence lookups) — ``find_parallel_config``
        silently drops rank-mismatched entries, so a rank-2 config on a
        rank-3 embedding would lose the host placement entirely."""
        return cls(DeviceType.CPU, (1,) * max(2, int(ndims)), (0,),
                   ("host", "host", "host"))

    @property
    def host_placed(self) -> bool:
        """True when this config requests host placement: CPU device
        type, or ANY region's memory type marked "host" (the runtime
        treats either as "weights live host-side" — model.py offload /
        row-sparse paths).  The SIMULATOR's host-tier pricing applies
        this only to Embedding ops (the row-sparse path); other
        host-placed ops stream weights but still compute on device."""
        return self.device_type == DeviceType.CPU \
            or "host" in self.memory_types

    @property
    def ndims(self) -> int:
        return len(self.dims)

    def num_parts(self) -> int:
        n = 1
        for d in self.dims:
            n *= d
        return n

    def with_device_ids(self, ids: Sequence[int]) -> "ParallelConfig":
        return dataclasses.replace(self, device_ids=tuple(ids))

    @staticmethod
    def data_parallel(ndims: int, num_devices: int) -> "ParallelConfig":
        """Default data-parallel config: split the batch (first) dim only.

        Mirrors ``FFModel``'s auto-installed DataParallelism_{1..4}D
        strategies (reference: src/runtime/model.cc:391-401) — sample dim
        split across all devices, every other dim unsplit.
        """
        dims = (num_devices,) + (1,) * (ndims - 1)
        return ParallelConfig(DeviceType.TPU, dims, tuple(range(num_devices)))


# Full original argv stashed by the module runner (__main__.py) before it
# rewrites sys.argv to the filtered list for the target script.
_RUNNER_ARGV: Optional[List[str]] = None


def set_runner_argv(argv: Sequence[str]) -> None:
    global _RUNNER_ARGV
    _RUNNER_ARGV = list(argv)


def _env_default_devices() -> int:
    """All devices of the default backend.  A backend that fails to
    initialise raises here — guessing "one device" would start a
    training run on hardware nobody asked for."""
    import jax

    return len(jax.devices())


@dataclasses.dataclass
class FFConfig:
    """Run-level configuration (reference: include/config.h:66-103).

    Defaults follow ``FFConfig::FFConfig`` / ``parse_args``
    (src/runtime/model.cc:1230-1342): batchSize 64, epochs 1, lr 0.01,
    wd 1e-4, search budget 0 (no search), alpha 0.05.
    """

    epochs: int = 1
    batch_size: int = 64
    iterations: int = -1  # -1: derive from dataset size
    print_freq: int = 10
    num_nodes: int = 1
    workers_per_node: int = 0  # 0 → all visible devices
    learning_rate: float = 0.01
    weight_decay: float = 0.0001
    synthetic_input: bool = False
    profiling: bool = False
    search_budget: int = 0
    search_alpha: float = 0.05
    search_overlap_backward_update: bool = False
    # Search engine: "" = auto (native C++ anneal, falling back to the
    # Python MCMC), "mcmc" = force the Python single chain, "population"
    # = parallel-tempered population of delta-simulator chains
    # (simulator/population.py; FF_SEARCH_* knobs tune it).
    search_engine: str = ""
    # Also search pipeline stage assignments during compile() and apply
    # the plan when it beats the best dim strategy (set_pipeline).
    search_pipeline: bool = False
    # Gradient accumulation: split each staged batch into K micro-batches
    # inside the jitted step (lax.scan; one micro's activations live at a
    # time), average grads, apply the optimizer once.
    grad_accum_steps: int = 1
    # Rematerialization: jax.checkpoint around weighted ops' forwards in
    # the train step — recompute activations in backward instead of
    # keeping them resident (FLOPs for HBM).
    remat: bool = False
    dataset_path: str = ""
    import_strategy_file: str = ""
    # Set when importing a file produced by the reference implementation,
    # whose dims are in Legion adim order (innermost first); this
    # framework's files use natural order (batch first).
    import_strategy_reference_order: bool = False
    export_strategy_file: str = ""
    seed: int = 0
    # Numerics: params kept in float32; activations computed in
    # ``compute_dtype`` (bfloat16 is the TPU-native default for benchmarks,
    # float32 for numerics tests).
    compute_dtype: str = "float32"
    # Route optimizer updates through the fused Pallas kernels
    # (kernels/fused_optimizer.py ≈ reference optimizer_kernel.cu); on a
    # mesh each parameter updates per-shard via a per-leaf shard_map.
    fused_optimizer: bool = False
    # ZeRO-1: shard optimizer state (momentum / Adam moments) over the
    # mesh axes the parameter itself does not occupy — replicated-param
    # state drops to ~1/N per device.  Beyond the reference (SURVEY §2.3
    # lists ZeRO-style optimizer sharding as design headroom).
    zero_optimizer: bool = False
    # Row-sparse host-resident embedding tables for host-placed Embedding
    # ops (reference: embedding.cc CPU tasks + dlrm_strategy_hetero.cc):
    # per step only the batch's unique rows move host<->device.  None =
    # auto (on exactly when sparse == dense numerics: plain SGD); True
    # forces lazy per-touched-row updates under momentum/Adam; False
    # always streams the full table.
    sparse_host_embeddings: Optional[bool] = None
    # Structured telemetry (observability/): step spans, phase spans,
    # throughput/MFU counters to a JSONL trace.  ``FF_TELEMETRY=1`` in
    # the environment enables it too; ``telemetry_file`` (or
    # ``FF_TELEMETRY_FILE``) overrides the default ff_trace.jsonl.
    telemetry: bool = False
    telemetry_file: str = ""
    # Per-op strategies, keyed by op name (the reference keys an equivalent
    # map by hash(op name) — include/config.h:102, strategy.cc:23-26; the
    # hash is an implementation detail of Legion mapper tags that the TPU
    # build does not need).
    strategies: Dict[str, ParallelConfig] = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        if self.workers_per_node == 0:
            self.workers_per_node = _env_default_devices()

    @property
    def num_devices(self) -> int:
        return self.num_nodes * self.workers_per_node

    # -- CLI ---------------------------------------------------------------
    def parse_args(self, argv: Optional[List[str]] = None) -> List[str]:
        """Parse reference-style CLI flags; returns unrecognized args.

        Mirrors FFConfig::parse_args (src/runtime/model.cc:1274-1342) plus
        the Legion ``-ll:*`` device flags that the reference passes through
        (``-ll:gpu`` → ``-ll:tpu``).
        """
        if argv is None:
            # The module runner (``python -m flexflow_tpu script ...``)
            # rewrites sys.argv to the FILTERED args but stashes the full
            # original list here so framework flags stay reachable.
            if _RUNNER_ARGV is not None:
                argv = _RUNNER_ARGV
            else:
                import sys

                argv = sys.argv[1:]
        argv = list(argv)
        rest: List[str] = []
        i = 0

        def take() -> str:
            nonlocal i
            i += 1
            return argv[i]

        while i < len(argv):
            a = argv[i]
            if a in ("-e", "--epochs"):
                self.epochs = int(take())
            elif a in ("-b", "--batch-size"):
                self.batch_size = int(take())
            elif a in ("--lr", "--learning-rate"):
                self.learning_rate = float(take())
            elif a in ("--wd", "--weight-decay"):
                self.weight_decay = float(take())
            elif a in ("--iterations",):
                self.iterations = int(take())
            elif a in ("--budget", "--search-budget"):
                self.search_budget = int(take())
            elif a in ("--alpha", "--search-alpha"):
                self.search_alpha = float(take())
            elif a in ("--overlap",):
                self.search_overlap_backward_update = True
            elif a in ("--import", "--import-strategy"):
                self.import_strategy_file = take()
            elif a in ("--import-reference-order",):
                self.import_strategy_reference_order = True
            elif a in ("--export", "--export-strategy"):
                self.export_strategy_file = take()
            elif a in ("--dataset", "-d"):
                self.dataset_path = take()
            elif a in ("--synthetic",):
                self.synthetic_input = True
            elif a in ("--profiling",):
                self.profiling = True
            elif a in ("--nodes",):
                self.num_nodes = int(take())
            elif a in ("-ll:tpu", "-ll:gpu"):
                self.workers_per_node = int(take())
            elif a in ("-ll:cpu", "-ll:util", "-ll:py", "-ll:fsize", "-ll:zsize", "-lg:prof"):
                take()  # accepted for compatibility, no-op on TPU
            elif a == "--seed":
                self.seed = int(take())
            elif a == "--bf16":
                self.compute_dtype = "bfloat16"
            elif a == "--fused-optimizer":
                self.fused_optimizer = True
            elif a == "--zero-optimizer":
                self.zero_optimizer = True
            elif a == "--search-pipeline":
                self.search_pipeline = True
            elif a == "--search-engine":
                self.search_engine = take()
            elif a == "--grad-accum":
                self.grad_accum_steps = int(take())
            elif a == "--remat":
                self.remat = True
            elif a == "--sparse-host-embeddings":
                # force lazy row-sparse host tables even under
                # momentum/Adam (auto mode only sparsifies plain SGD)
                self.sparse_host_embeddings = True
            elif a == "--no-sparse-host-embeddings":
                self.sparse_host_embeddings = False
            elif a == "--telemetry":
                self.telemetry = True
            elif a == "--telemetry-file":
                self.telemetry = True
                self.telemetry_file = take()
            else:
                rest.append(a)
            i += 1
        return rest

    # -- strategy lookup ---------------------------------------------------
    def find_parallel_config(self, ndims: int, pcname: str) -> ParallelConfig:
        """Look up an op's config, falling back to data parallelism.

        Reference semantics (src/runtime/strategy.cc:28-85): exact-name hit
        must match dimensionality; otherwise fall back to the default
        data-parallel config of the right rank over all devices.
        """
        pc = self.strategies.get(pcname)
        if pc is not None:
            if pc.ndims == ndims:
                return pc
            # Rank-mismatched entry: reference asserts; we degrade to DP.
        return ParallelConfig.data_parallel(ndims, self.num_devices)
