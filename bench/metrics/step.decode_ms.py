"""Device milliseconds per decode step: the profiler's time inside the
jitted decode program (``jit_decode``) over its executions."""


def read(run):
    t, n = run.device["modules"].get("jit_decode", (0.0, 0))
    return 1e3 * t / n if n else None
