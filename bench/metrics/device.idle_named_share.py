"""Share of the device's idle time between programs that a scheduler span
of host work names: the union of the ``sched.*`` spans other than the
iteration that holds them and the ``*.wait`` spans (the host standing on a
program's tokens), over the gaps between programs on the first chip, with
the spans moved onto the device's clock (``hosttrace.clock_shift``).  The
reader also adds the span that covers most of each gap, a wait among
them, to the breakdown's ``idle_gaps``, the list the harness prints after
the readers run (``after jit_decode: sched.prefill.prepare``)."""
import hosttrace


def read(run):
    trace = hosttrace.window(run)
    if trace is None:
        return None
    hosttrace.label_gaps(run.device["idle_gaps"], trace)
    return hosttrace.idle_named_share(trace)
