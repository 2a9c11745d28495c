"""repro.quant: quantized frozen-backbone subsystem.

The Hadamard adapter keeps 99.967% of a deployment's weights frozen; this
package compresses that invariant once and shares it everywhere: a
QTensor (values + scales) pytree leaf, per-channel symmetric int8 / fp8
weight quantization of the backbone's matmul projections, an activation-
statistics calibration pass, and the `qdense` entry point that routes
QTensor weights through the fused Pallas dequant-matmul kernel
(kernels/quant.py). Serving (`ServeEngine(..., quant="int8")`), QPEFT
training (`make_state(..., quant=...)`), sharding, and checkpointing all
consume the same representation.
"""
from repro.quant.calibrate import calibrate, collect_stats
from repro.quant.qtensor import (
    QTensor,
    QUANT_MODES,
    QUANT_PATTERNS,
    dequantize_tree,
    fake_quantize,
    is_qtensor,
    qdense,
    quant_summary,
    quantization_error,
    quantize,
    quantize_tree,
)

__all__ = [
    "QTensor",
    "QUANT_MODES",
    "QUANT_PATTERNS",
    "calibrate",
    "collect_stats",
    "dequantize_tree",
    "fake_quantize",
    "is_qtensor",
    "qdense",
    "quant_summary",
    "quantization_error",
    "quantize",
    "quantize_tree",
]
