"""Operations and bytes, computed from shapes, and the peaks they are
held against. Nothing here reads a clock: times come from the trace.

- ``int8_matmul``: one call of the centered int8 matmul kernel
  (``x (M, K) int8 @ w (K, N) int8 -> (M, N) int32``, plus the per-column
  center term): ``2 M K N`` operations; the least bytes are both operands
  once, the int32 centers and the int32 result.
- ``model_ops``: what the served model needs for the tokens a window
  made, split by the peak it is held against: the int8 projections and
  head of the ``fast`` path, and bfloat16 attention over each token's
  real context (not the padded view the program gathers).
"""

from __future__ import annotations

from weights import head_dim


def int8_matmul(m: int, k: int, n: int) -> tuple[float, float]:
    """(operations, bytes) of one centered int8 matmul call."""
    return 2.0 * m * k * n, float(m * k + k * n + 4 * n + 4 * m * n)


def roofline_s(ops: float, nbytes: float, peak: dict,
               ops_key: str = "int8_ops_per_s") -> tuple[float, str]:
    """Least time the chip could take, and which bound sets it."""
    t_ops, t_mem = ops / peak[ops_key], nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")


def proj_ops_per_token(cfg: dict) -> float:
    """int8 operations of one token through every layer's projections."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hq = cfg["num_attention_heads"] * head_dim(cfg)
    hk = cfg["num_key_value_heads"] * head_dim(cfg)
    per_layer = d * hq + 2 * d * hk + hq * d + 3 * d * f
    return 2.0 * per_layer * cfg["num_hidden_layers"]


def head_ops(cfg: dict) -> float:
    return 2.0 * cfg["hidden_size"] * cfg["vocab_size"]


def attn_ops(cfg: dict, n_keys: int) -> float:
    """bfloat16 operations of one query attending ``n_keys`` keys in every
    layer (scores and the weighted sum of values)."""
    return (4.0 * cfg["num_attention_heads"] * head_dim(cfg) * n_keys
            * cfg["num_hidden_layers"])


def model_ops(cfg: dict, prefill: list, decode: list) -> tuple[float, float]:
    """(int8 ops, bf16 ops) for prefill chunks ``(lo, hi, last)`` (``last``:
    the chunk ends the prompt, so the head runs once) and decode tokens
    given by their position."""
    i8 = bf = 0.0
    for lo, hi, last in prefill:
        i8 += (hi - lo) * proj_ops_per_token(cfg) + (head_ops(cfg) if last
                                                     else 0.0)
        # the token at position p attends p + 1 keys
        bf += attn_ops(cfg, (hi * (hi + 1) - lo * (lo + 1)) // 2)
    for pos in decode:
        i8 += proj_ops_per_token(cfg) + head_ops(cfg)
        bf += attn_ops(cfg, pos + 1)
    return i8, bf
