"""Share of the device's idle time between programs that a scheduler span
of host work names: the union of the ``sched.*`` spans other than the
iteration that holds them and the ``*.wait`` spans (the host standing on a
program's tokens), over the gaps between programs on the first chip, with
the spans moved onto the device's clock (``hosttrace.clock_shift``)."""
import hosttrace


def read(run):
    trace = hosttrace.window(run)
    return None if trace is None else hosttrace.idle_named_share(trace)
