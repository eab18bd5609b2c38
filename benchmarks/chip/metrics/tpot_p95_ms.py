"""95th percentile of the gaps between consecutive output tokens of one
request, over every gap that ends inside the window (host clock)."""

import stats


def read(run):
    if not run.due:
        return None
    gaps = stats.token_gaps(run.token_times, run.t0, run.t_end)
    return stats.percentile(gaps, 0.95) * 1e3 if gaps else None
