"""Share of its roofline that the centered int8 matmul kernel reached in
the traced window: the least time its calls could take on this chip
(operations over the int8 peak or bytes over the HBM bandwidth, whichever
is larger, from each call's shapes) over their summed device time."""

import trace_reduce


def read(run):
    if run.trace is None or run.peak is None:
        return None
    return trace_reduce.int8_matmul_roofline_pct(run.trace, run.peak)
