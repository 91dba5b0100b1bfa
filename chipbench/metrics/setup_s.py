"""Seconds from process start to the window's opening: weights, engine,
compilation or cache loads, warm-up."""


def read(run):
    return run.setup_s
