"""Share of its roofline the muxq_gemm kernel reaches: the least time the
four projections of every layer need for the window's valid tokens at
unpadded widths (int8 operations at the int8 peak, or their bytes at HBM
bandwidth, whichever is longer), over the device time of the kernel's
events in the trace."""
import tracefile
import work


def read(run):
    t = tracefile.kernel_s(run.device, "muxq_gemm")
    if not t:
        return None
    w, p = run.work, run.peaks
    need = work.roofline_s(w["linear_ops"], w["linear_bytes"],
                           p["int8_ops_per_s"], p["hbm_bytes_per_s"])
    return 100.0 * need / t
