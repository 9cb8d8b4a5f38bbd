"""Share of the pages in the paged_attention kernel's grid that it reads:
the scheduler's ServeMetrics ``attn_pages_live`` (each slot's pages up to
the one holding its last query row) over ``attn_pages_bucket`` (slots
times the page bucket of each dispatch), over the window.  The rest are
the dead grid steps the kernel walks past."""


def read(run):
    c = run.counters
    if not c.get("attn_pages_bucket"):
        return None
    return 100.0 * c["attn_pages_live"] / c["attn_pages_bucket"]
