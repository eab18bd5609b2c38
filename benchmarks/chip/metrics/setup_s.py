"""Process start to the window's opening: weights, PIM plan compile,
engine, warm-up of every shape the traffic drives (host clock)."""


def read(run):
    return run.setup_s
