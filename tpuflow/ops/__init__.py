"""TPU compute kernels: XLA-fused ops and Pallas kernels for the hot paths."""

from tpuflow.ops.attention import (
    attention,
    resolve_attention_impl,
    xla_attention,
)
from tpuflow.ops.grouped_matmul import grouped_dot, resolve_grouped_impl
from tpuflow.ops.int8_matmul import (
    int8_matmul,
    quantize_rows,
    resolve_int8_impl,
)

__all__ = [
    "attention",
    "grouped_dot",
    "int8_matmul",
    "quantize_rows",
    "resolve_attention_impl",
    "resolve_grouped_impl",
    "resolve_int8_impl",
    "xla_attention",
]
