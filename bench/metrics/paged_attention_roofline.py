"""Share of its roofline the paged_attention kernel reaches: the least
time for the cached bytes of the live positions each call attends (not
the page bucket it walks; in a windowed layer only its window) and their
operations at the bf16 peak, as ``work.py`` counts them from the
reference's description, over the device time of the kernel's events in
the trace."""
import tracefile
import work


def read(run):
    t = tracefile.kernel_s(run.device, "paged_attention")
    if not t:
        return None
    w, p = run.work, run.peaks
    need = work.roofline_s(w["attention_ops"], w["attention_bytes"],
                           p["bf16_flops_per_s"], p["hbm_bytes_per_s"])
    return 100.0 * need / t
