from .attention import dot_product_attention
from .kernel_forms import traced
from .layer_norm import layer_norm, supports_fused_ln
from .quant_matmul import int8_matmul, quantize_rowwise, supports_q8_kernel

__all__ = [
    "dot_product_attention",
    "int8_matmul",
    "layer_norm",
    "quantize_rowwise",
    "supports_fused_ln",
    "supports_q8_kernel",
    "traced",
]
