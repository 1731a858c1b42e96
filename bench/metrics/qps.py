"""Queries answered in the window over the window's length."""


def read(run):
    return run["n_completed"] / run["window_s"]
