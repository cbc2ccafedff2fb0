"""Seconds from the process's start to the window's opening: loading,
weights, compiling or loading programs from the cache, warm-up and the
traffic's ramp."""


def read(run):
    return run.setup_s
