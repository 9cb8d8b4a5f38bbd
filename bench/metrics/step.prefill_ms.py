"""Device milliseconds per prefill step: the profiler's time inside the
jitted chunked-prefill program (``jit_prefill``) over its executions."""


def read(run):
    t, n = run.device["modules"].get("jit_prefill", (0.0, 0))
    return 1e3 * t / n if n else None
