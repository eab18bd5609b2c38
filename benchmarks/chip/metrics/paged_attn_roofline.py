"""Share of its roofline that the paged decode attention kernel reached
in the traced window: the least time its work could take on this chip
over the summed device time of its calls (the ``paged_decode_attention``
ops).

The work is the family's ``decode_attention_work`` for the decode
tokens the host received in the traced span, taken as ``step_mfu`` takes
them (a token at position p attends p + 1 keys); for the dense decoder,
in every layer, each key's K and V read once in bfloat16, plus the
token's query and output. At H / K operations per byte, against the
chip's hundreds, every call is memory bound. A trace without the kernel
reads nothing."""

import roofline

KERNEL = "%paged_decode_attention"


def decode_positions(run) -> list[int]:
    """Positions of the decode tokens received in the traced span."""
    a, b = run.trace.host_span
    return [run.prompt_len[u] + k - 1
            for u, times in run.token_times.items()
            for k, t in enumerate(times) if k > 0 and a <= t < b]


def read(run):
    t = run.trace
    if t is None or run.peak is None or t.host_span is None:
        return None
    device_ns = sum(d for name, _, d in t._in_window(t.ops)
                    if name.startswith(KERNEL))
    positions = decode_positions(run)
    if not device_ns or not positions:
        return None
    work = run.family.decode_attention_work(run.model, positions)
    least, _ = roofline.roofline_s(*work, run.peak, "bf16_flops_per_s")
    return 100.0 * least / (device_ns * 1e-9)
