"""Low-level op helpers shared by compute units."""

from .precision import (matmul_precision, quantize_int8,  # noqa: F401
                        dequantize_int8, quantize_rows_int8,
                        dequantize_rows_int8)
