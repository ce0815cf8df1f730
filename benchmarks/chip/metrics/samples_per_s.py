"""Samples the consumer finished in the window over the window's length."""


def read(run):
    return run.samples / run.window_s
