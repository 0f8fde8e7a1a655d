from repro.kernels.quantize.ops import payload_quantize_dequantize
from repro.kernels.quantize import ref

__all__ = [
    "payload_quantize_dequantize",
    "ref",
]
