"""Pallas TPU kernels for the hot ops.

The reference implements its hot paths as hand-written CUDA kernels
(src/ops/*.cu, src/runtime/optimizer_kernel.cu).  The TPU-native
equivalent: XLA already fuses the elementwise graph, so custom kernels
are reserved for the ops where manual VMEM scheduling beats the
compiler — blockwise (flash) attention (``flash_attention``: the
``flash_*``, ``flash_win_*`` and ``flash_sel_*`` kernels), the routed
experts' grouped products (``grouped_matmul``: ``gmm``, ``gmm_t``,
``tgmm``), the learned index's scores and their gradient (``dsa_index``:
``dsa_index_fwd``, ``dsa_index_bwd``) and the fused optimizer updates.
Each ``pallas_call`` is named as the scope it runs under,
``ff.kernel.<name>``.
"""

from .flash_attention import flash_attention, mha_reference
from .fused_optimizer import fused_sgd_update, fused_adam_update

__all__ = [
    "flash_attention",
    "mha_reference",
    "fused_sgd_update",
    "fused_adam_update",
]
