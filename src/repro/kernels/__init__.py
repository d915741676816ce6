"""Pallas TPU kernels for the paper's compute hot-spot: the LNS ⊞-MAC.

``lns_matmul`` — blocked multiplication-free matmul (+ fused flush-time
epilogues: bias ⊞ / llrelu / requantize in the forward kernel, the ⊞-SGD
update in the dW kernel, and the standalone fused-update kernel);
``lns_boxsum`` — the soft-max Σ⊞ reduction (eq. 14) and the DP combine
(max + Δ-LUT / bit-shift accumulation on the VPU);
``autotune``   — the per-(spec, op, shape) block-size autotuner behind
the ``blocks=auto`` spec axis.  Validated bit-exactly against ``ref.py``,
interpreted on the CPU and compiled on a TPU (``core.lns.resolve_interpret``
picks from the platform).
"""
from . import autotune
from .lns_boxsum import lns_boxsum_kernel, lns_boxsum_ref
from .lns_matmul import (FwdEpilogue, lns_fused_update_kernel,
                         lns_matmul_dw_kernel, lns_matmul_dw_partials_kernel,
                         lns_matmul_dw_partials_ref, lns_matmul_dw_ref,
                         lns_matmul_dw_update_kernel,
                         lns_matmul_dw_update_ref, lns_matmul_dx_kernel,
                         lns_matmul_dx_ref, lns_matmul_fused_kernel,
                         lns_matmul_fused_ref, lns_matmul_kernel,
                         lns_matmul_ref, lns_matmul_trainable)

__all__ = ["autotune", "FwdEpilogue",
           "lns_boxsum_kernel", "lns_boxsum_ref",
           "lns_matmul_kernel", "lns_matmul_ref",
           "lns_matmul_dx_kernel", "lns_matmul_dx_ref",
           "lns_matmul_dw_kernel", "lns_matmul_dw_ref",
           "lns_matmul_dw_partials_kernel", "lns_matmul_dw_partials_ref",
           "lns_matmul_fused_kernel", "lns_matmul_fused_ref",
           "lns_matmul_dw_update_kernel", "lns_matmul_dw_update_ref",
           "lns_fused_update_kernel", "lns_matmul_trainable"]
