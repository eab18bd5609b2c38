"""Device idle time per decode step while the host runs the serve
engine's own code: the idle time of the traced window that lies inside a
``serve.step`` span and outside every jitted dispatch (the
``decode_step`` and ``prefill_chunk`` step annotations), over the number
of ``decode_step`` annotations that start in the window. Idle time inside
a dispatch, such as an allocator wait, belongs to other layers. A program
that opens no ``serve.step`` span reads nothing."""

import trace_reduce as tr

STEP = "serve.step"
DISPATCH = ("decode_step", "prefill_chunk")


def _overlap_ns(a, b) -> float:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def read(run):
    t = run.trace
    if t is None or not t.busy_s:
        return None
    w0, w1 = t.window
    n_decode = sum(1 for name, s, _ in t.host
                   if name == "decode_step" and w0 <= s < w1)
    if not n_decode:
        return None
    steps = tr.busy_intervals([e for e in t.host if e[0] == STEP], t.window)
    if not steps:
        return None
    dispatch = [e for e in t.host if e[0] in DISPATCH]
    engine_only = [g for s in steps for g in tr.idle_gaps(dispatch, s)]
    idle = tr.idle_gaps(t.ops, t.window)
    return _overlap_ns(idle, engine_only) * 1e-6 / n_decode
