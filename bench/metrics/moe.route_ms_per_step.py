"""Device milliseconds per step call in ops under the step programs' ``moe``
name scope, less the ``muxq_gemm`` kernel's (self time): routing, the sort
of the routed rows into expert tiles, their gather, the rows' quantize and
the weighted combine."""
import hosttrace
import tracefile

MOE_SCOPE = "moe"
KERNEL = "muxq_gemm"


def read(run):
    trace = hosttrace.window(run)
    if trace is None:
        return None
    records = trace["records"]
    evs = {}
    for r in records:
        if r["line"] == tracefile.OPS:
            hit = (MOE_SCOPE in r.get("scope", "").split("/")[:-1]
                   and tracefile.op_name(r["name"]) != KERNEL)
            evs.setdefault(r["chip"], []).append(
                (r["start_ns"], r["start_ns"] + r["dur_ns"], hit))
    calls = sum(1 for r in records if r["line"] == tracefile.MODULES
                and tracefile._module(r["name"]) in hosttrace.STEP_PROGRAMS)
    if not calls or not any(hit for ops in evs.values() for *_, hit in ops):
        return None
    ns = sum(t for ops in evs.values()
             for hit, t in tracefile._self_times(ops) if hit)
    return ns * 1e-6 / calls
