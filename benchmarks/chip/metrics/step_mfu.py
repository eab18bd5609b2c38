"""Whole-step share of the chip's peak over the traced window, on the
host clock: the model's int8 projection and head operations over the
int8 peak, plus its bfloat16 attention operations (over each token's
real context) over the bf16 peak, for the tokens the host received in
the traced window, over that window's length."""

import trace_reduce


def read(run):
    if run.trace is None or run.peak is None:
        return None
    return trace_reduce.step_mfu_pct(run, run.peak)
