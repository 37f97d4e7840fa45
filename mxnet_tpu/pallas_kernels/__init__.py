"""Pallas TPU kernels — the hand-written hot path.

Reference counterpart: MXNet's fused CUDA kernels
(`src/operator/contrib/transformer.cc`, `src/operator/fusion/`) and NVRTC
runtime fusion. On TPU, XLA already fuses elementwise chains; what pays here
is flash attention (O(L) memory softmax-attention streaming K/V blocks
through VMEM) — the enabler for long sequences — plus the `mx.pallas`
user-kernel surface (the `mx.rtc.CudaModule` capability re-imagined,
see mxnet_tpu.pallas_api).
"""
from .flash_attention import (flash_attention, flash_attention_scan,
                              flash_supported, flash_shape_supported)
from .fused_layers import (fused_bias_gelu, fused_layer_norm,
                           fused_layers_enabled, fused_ln_shape_supported,
                           fused_ln_supported, fused_rms_norm)
from .fused_optimizer import (fused_opt_enabled, fused_opt_supported,
                              sweep_pallas)
from .paged_attention import (mla_paged_decode_kernel,
                              mla_paged_shape_supported,
                              mla_paged_supported, paged_attention_kernel,
                              paged_shape_supported, paged_supported)

__all__ = ["flash_attention", "flash_attention_scan", "flash_supported",
           "flash_shape_supported", "fused_layer_norm", "fused_rms_norm",
           "fused_bias_gelu", "fused_layers_enabled",
           "fused_ln_shape_supported", "fused_ln_supported",
           "fused_opt_enabled", "fused_opt_supported", "sweep_pallas",
           "paged_attention_kernel", "paged_shape_supported",
           "paged_supported", "mla_paged_decode_kernel",
           "mla_paged_shape_supported", "mla_paged_supported"]
