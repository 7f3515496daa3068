"""Seconds from the process's start to the window's: imports, the chip,
inputs and weights, compiles (or reading them from the cache), warming
every drain length, connecting the volunteers and their first tickets."""


def read(run):
    return run.setup_s
