"""Share of its roofline that the paged decode attention kernel reached
in the traced window: the least time its work could take on this chip
over the summed device time of its calls (the ``paged_decode_attention``
ops).

The work is that of the decode tokens the host received in the traced
span, taken as ``step_mfu`` takes them (a token at position p attends
p + 1 keys): in every layer, each key's K and V read once in bfloat16,
plus the token's query and output. At H / K operations per byte, against
the chip's hundreds, every call is memory bound. A trace without the
kernel reads nothing."""

import roofline
from weights import head_dim

KERNEL = "%paged_decode_attention"
BF16_BYTES = 2


def decode_positions(run) -> list[int]:
    """Positions of the decode tokens received in the traced span."""
    a, b = run.trace.host_span
    return [run.prompt_len[u] + k - 1
            for u, times in run.token_times.items()
            for k, t in enumerate(times) if k > 0 and a <= t < b]


def work(cfg: dict, positions: list[int]) -> tuple[float, float]:
    """(operations, bytes) of decode attention for tokens at
    ``positions``, over every layer."""
    hd, layers = head_dim(cfg), cfg["num_hidden_layers"]
    keys = sum(p + 1 for p in positions)
    kv = 2 * cfg["num_key_value_heads"] * hd * keys
    q_out = 2 * cfg["num_attention_heads"] * hd * len(positions)
    return (roofline.attn_ops(cfg, keys),
            float(layers * BF16_BYTES * (kv + q_out)))


def read(run):
    t = run.trace
    if t is None or run.peak is None or t.host_span is None:
        return None
    device_ns = sum(d for name, _, d in t._in_window(t.ops)
                    if name.startswith(KERNEL))
    positions = decode_positions(run)
    if not device_ns or not positions:
        return None
    least, _ = roofline.roofline_s(*work(run.model, positions), run.peak,
                                   "bf16_flops_per_s")
    return 100.0 * least / (device_ns * 1e-9)
