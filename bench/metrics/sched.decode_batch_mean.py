"""Mean active slots per decode step: ServeMetrics decode_slot_steps over
decode_steps (counts kept by the scheduler)."""


def read(run):
    c = run.counters
    if not c["decode_steps"]:
        return None
    return c["decode_slot_steps"] / c["decode_steps"]
