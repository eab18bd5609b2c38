"""Useful (non-padding) slots per batched decode step over the window,
from the program's ``ServeStats`` counters at its opening and close."""


def read(run):
    a, b = run.stats_at_open, run.stats_at_close
    if not a or not b or b["decode_steps"] == a["decode_steps"]:
        return None
    return ((b["decode_slot_tokens"] - a["decode_slot_tokens"])
            / (b["decode_steps"] - a["decode_steps"]))
