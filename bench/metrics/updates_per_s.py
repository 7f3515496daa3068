"""Commits the volunteers received in the window, per second of it."""


def read(run):
    return run.window_commits / run.window_s
