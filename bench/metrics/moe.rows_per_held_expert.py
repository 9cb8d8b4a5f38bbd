"""Rows each held expert computes in one expert-layer call: the routed rows
of live tokens that landed on a held expert over the expert-layer calls
times the experts held (the configuration's ``n_routed_experts``).  Both
counts come from the step programs, beside each step's tokens, and ride
the scheduler's step records (``moe_rows_held``, ``moe_expert_calls``)."""


def read(run):
    steps = [e["args"] for e in run.events if e["name"] == "STEP"]
    rows = sum(a.get("moe_rows_held", 0) for a in steps)
    calls = sum(a.get("moe_expert_calls", 0) for a in steps)
    held = run.spec.get("n_routed_experts")
    if not calls or not held:
        return None
    return rows / (calls * held)
