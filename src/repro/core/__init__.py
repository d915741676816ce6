"""Core LNS library — the paper's contribution.

Sub-modules:
  formats        fixed-point format descriptors (+ eq. 15 bit-width bound)
  lns            LNSArray pytree + float codecs + matmul backend dispatcher
  delta          Δ± exact / LUT / bit-shift engines (paper Sec. 3)
  arithmetic     ⊡ ⊞ ⊟, reductions, emulated log-domain matmul (eq. 10)
  conversions    log ↔ linear fixed point (Mitchell / LUT / exact)
  activations    log-leaky-ReLU + derivative (eq. 11)
  softmax        log-domain softmax + CE gradient init (eq. 14)
  initializers   log-domain weight init (eq. 12)
  linear_fixed   linear-domain fixed-point baseline arithmetic
  sgd            pure-LNS SGD (+momentum, weight decay)
  qat            straight-through LNS quantization / emulated-MAC dot
  spec           NumericsSpec / ReduceSpec / LNSRuntime — the unified
                 serializable numerics descriptor and its resolution
  plan           NumericsPlan — per-layer glob patterns → spec overrides
                 (mixed-format training across the model stack)
  numerics       alias registry over spec (fp32/bf16/lns*) + get_policy
"""
from .arithmetic import (bias_add, boxabs_max, boxdiv, boxdot, boxminus,
                         boxneg, boxplus, boxsum, boxsum_partials,
                         lns_affine, lns_matmul)
from .activations import (beta_code, llrelu, llrelu_grad,
                          llrelu_grad_from_sign)
from .conversions import code_to_lns, lns_value_to_code
from .delta import (DELTA_BITSHIFT, DELTA_DEFAULT, DELTA_EXACT, DELTA_SOFTMAX,
                    DeltaEngine, DeltaSpec, delta_minus_float,
                    delta_plus_float)
from .formats import (FORMATS, FXP12, FXP16, LNS12, LNS16, LNS21,
                      FixedPointFormat, LNSFormat, required_log_width)
from .initializers import (encode_init, he_sigma, log_density_normal,
                           log_normal_init)
from .lns import (BLOCK_MODES, MATMUL_BACKENDS, LNSArray, LNSMatmulBackend,
                  convert_format, decode, encode, from_parts, parse_blocks,
                  quantization_bound, resolve_blocks_arg, scalar, zeros)
from .numerics import POLICIES, NumericsPolicy, get_plan, get_policy
from .plan import NumericsPlan, PlanRule, plan_diff
from .qat import lns_dot_dispatch, lns_dot_exact, lns_quantize_ste
from .spec import (ALIASES, INTERPRET_MODES, REDUCE_MODES, REDUCE_SCHEDULES,
                   LNSRuntime, NumericsSpec, ReduceSpec)
from .sgd import (LogSGDConfig, UpdateEpilogue, apply_update,
                  apply_update_codes, init_momentum)
from .softmax import ce_grad_init, ce_loss_readout, log_softmax_lns

__all__ = [n for n in dir() if not n.startswith("_")]
