"""Share of the rows a prefill step computes that hold prompt tokens:
ServeMetrics prefill_chunk_tokens over the rows computed, which is the
pool's slots times the chunk bucket of each scheduler STEP record that
ran a prefill (the call always runs every slot at the bucket's width)."""


def read(run):
    rows = sum(run.n_slots * e["args"]["chunk_bucket"] for e in run.events
               if e["name"] == "STEP" and e["args"].get("chunk_bucket"))
    if not rows:
        return None
    return 100.0 * run.counters["prefill_chunk_tokens"] / rows
