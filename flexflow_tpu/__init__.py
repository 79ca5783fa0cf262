"""flexflow_tpu — a TPU-native FlexFlow-class training framework.

A from-scratch re-design of early (Legion-era, MLSys'19 "SOAP") FlexFlow
for TPUs: layer-graph model building, per-operator hybrid parallelization
over sample/attribute/parameter dimensions via strategy files, an execution
simulator + MCMC search for automatic parallelization, and end-to-end
training — all lowering to JAX/XLA SPMD over device meshes instead of
Legion tasks + cuDNN kernels.  See SURVEY.md at the repo root for the full
reference inventory this framework mirrors.
"""

from .config import DeviceType, FFConfig, ParallelConfig
from .initializers import (ConstantInitializer, GlorotUniform, NormInitializer,
                           UniformInitializer, ZeroInitializer)
from .losses import Loss, LossType
from .metrics import MetricsType, PerfMetrics
from .model import FFModel
from .ops.base import Op
from .ops.conv2d import ActiMode, PoolType
from .ops.embedding import AggrMode
from .optimizers import (AdamOptimizer, OptaxOptimizer, Optimizer,
                         SGDOptimizer)
from .parallel.mesh import Machine
from .parallel.strategy import load_strategies_from_file, save_strategies_to_file
from .runtime.dataloader import DataLoader
from .tensor import DataType, Parameter, Tensor

__version__ = "0.6.0"

__all__ = [
    "ActiMode", "AdamOptimizer", "AggrMode", "ConstantInitializer",
    "DataLoader", "DataType", "DeviceType", "FFConfig", "FFModel",
    "GlorotUniform", "Loss", "LossType", "Machine", "MetricsType",
    "NormInitializer", "Op", "Optimizer", "Parameter", "ParallelConfig",
    "OptaxOptimizer", "PerfMetrics", "PoolType", "SGDOptimizer", "Tensor",
    "UniformInitializer", "ZeroInitializer", "load_strategies_from_file",
    "save_strategies_to_file",
]
