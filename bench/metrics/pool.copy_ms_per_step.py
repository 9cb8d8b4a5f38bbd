"""Device milliseconds per step call in ops under the step programs'
``pool`` name scope (self time): the page writes and the relayout of the
written pages into the paged kernel's operand layout."""
import hosttrace


def read(run):
    trace = hosttrace.window(run)
    return None if trace is None else hosttrace.pool_ms_per_step(
        trace["records"])
