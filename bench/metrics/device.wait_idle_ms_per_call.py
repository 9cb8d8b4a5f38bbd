"""Device idle milliseconds per step call under the scheduler's waits on a
program's tokens (``sched.*.wait``): the gaps between programs on the
first chip while the host stands in ``np.asarray`` on a finished
program's tokens, spans moved onto the device's clock
(``hosttrace.clock_shift``).  Dispatching the next step before the tokens
come back would take it away."""
import hosttrace


def read(run):
    trace = hosttrace.window(run)
    return None if trace is None else hosttrace.wait_idle_ms_per_call(trace)
