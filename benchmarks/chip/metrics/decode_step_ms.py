"""Device time of the jitted decode step: the summed device time of its
ops in the traced window over the number of decode steps there."""


def read(run):
    if run.trace is None:
        return None
    return run.trace.step_ms("decode_step")
