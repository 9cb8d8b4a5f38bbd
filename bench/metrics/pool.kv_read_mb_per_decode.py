"""KV page megabytes a decode step's bucket spans (1 MB = 1e6 bytes):
ServeMetrics kv_bytes_read, priced from page shapes over every slot's
whole bucketed page budget, over decode_steps.  The paged kernel stops at
each slot's last live page, so it reads only part of this: about a fifth
in the chat cells (``paged_attention.live_page_share``)."""


def read(run):
    c = run.counters
    if not c["decode_steps"]:
        return None
    return c["kv_bytes_read"] / c["decode_steps"] / 1e6
