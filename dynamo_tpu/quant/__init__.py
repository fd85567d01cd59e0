"""Quantization for the serving hot path.

``int8_wo`` (weights): symmetric per-output-channel int8 weights with f32
scales, dequantized into the matmul — see dynamo_tpu/quant/int8.py.
``kv_cache_dtype="int8"`` (cache): int8 KV pages with per-(page, token-row)
f32 scales — see dynamo_tpu/quant/kv.py. The two compose independently.
"""

from dynamo_tpu.quant.int8 import (
    QUANT_MODES,
    QuantizedLinear,
    dequantize_int8,
    qlinear,
    quantize_int8,
    quantize_shardings_int8,
    quantize_tree_int8,
)
from dynamo_tpu.quant.kv import (
    KV_CACHE_DTYPES,
    QuantizedPages,
    dequantize_rows,
    init_quantized_pages,
    kv_page_bytes,
    pages_for_hbm_budget,
    quantize_kv_rows,
)

__all__ = [
    "KV_CACHE_DTYPES",
    "QUANT_MODES",
    "QuantizedLinear",
    "QuantizedPages",
    "dequantize_int8",
    "dequantize_rows",
    "init_quantized_pages",
    "kv_page_bytes",
    "pages_for_hbm_budget",
    "qlinear",
    "quantize_int8",
    "quantize_kv_rows",
    "quantize_shardings_int8",
    "quantize_tree_int8",
]
