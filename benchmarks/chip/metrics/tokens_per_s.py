"""Output tokens committed inside the window, over its length (host
clock). Requests still running at the close count with what they made."""

import stats


def read(run):
    n = stats.tokens_between(run.token_times, run.t0, run.t_end)
    return n / (run.t_end - run.t0)
