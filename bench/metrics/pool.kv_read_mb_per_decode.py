"""KV page megabytes a decode step reads (1 MB = 1e6 bytes): ServeMetrics
kv_bytes_read, priced from page shapes over every slot's bucketed page
budget, over decode_steps."""


def read(run):
    c = run.counters
    if not c["decode_steps"]:
        return None
    return c["kv_bytes_read"] / c["decode_steps"] / 1e6
