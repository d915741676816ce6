from .lns_matmul import FwdEpilogue
from .ops import (lns_fused_update_kernel, lns_matmul_dw_kernel,
                  lns_matmul_dw_partials_kernel, lns_matmul_dw_update_kernel,
                  lns_matmul_dx_kernel, lns_matmul_fused_kernel,
                  lns_gmm_trainable, lns_matmul_kernel,
                  lns_matmul_trainable)
from .ref import (lns_matmul_dw_partials_ref, lns_matmul_dw_ref,
                  lns_matmul_dw_update_ref, lns_matmul_dx_ref,
                  lns_matmul_fused_ref, lns_matmul_ref)

__all__ = ["FwdEpilogue",
           "lns_matmul_kernel", "lns_matmul_dx_kernel",
           "lns_matmul_dw_kernel", "lns_matmul_dw_partials_kernel",
           "lns_matmul_fused_kernel", "lns_matmul_dw_update_kernel",
           "lns_fused_update_kernel", "lns_matmul_trainable",
           "lns_gmm_trainable",
           "lns_matmul_ref", "lns_matmul_dx_ref", "lns_matmul_dw_ref",
           "lns_matmul_dw_partials_ref", "lns_matmul_fused_ref",
           "lns_matmul_dw_update_ref"]
