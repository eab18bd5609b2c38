"""Operations and bytes, computed from shapes, and the peaks they are
held against. Nothing here reads a clock: times come from the trace.

- ``int8_matmul``: one call of the centered int8 matmul kernel
  (``x (M, K) int8 @ w (K, N) int8 -> (M, N) int32``, plus the per-column
  center term): ``2 M K N`` operations; the least bytes are both operands
  once, the int32 centers and the int32 result.

What a model needs for the tokens a window made is its family's count
(``families/``): ``model_ops`` and ``decode_attention_work``.
"""

from __future__ import annotations


def int8_matmul(m: int, k: int, n: int) -> tuple[float, float]:
    """(operations, bytes) of one centered int8 matmul call."""
    return 2.0 * m * k * n, float(m * k + k * n + 4 * n + 4 * m * n)


def roofline_s(ops: float, nbytes: float, peak: dict,
               ops_key: str = "int8_ops_per_s") -> tuple[float, str]:
    """Least time the chip could take, and which bound sets it."""
    t_ops, t_mem = ops / peak[ops_key], nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
