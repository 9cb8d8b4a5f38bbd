"""Host milliseconds per step call: in the profile, each scheduler
iteration span (``sched.iteration``) less its waits on a program's tokens
(``sched.*.wait``), summed over the window, over the step calls those
iterations dispatched.  What the host adds to every call."""
import hosttrace


def read(run):
    trace = hosttrace.window(run)
    return None if trace is None else hosttrace.host_ms_per_call(trace)
