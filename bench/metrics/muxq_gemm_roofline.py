"""Share of its roofline the muxq_gemm kernel reaches: the least time the
projections the reference's description marks quantized need for the
window's valid tokens at unpadded widths (int8 operations at the int8
peak, or their bytes at HBM bandwidth, whichever is longer), over the
device time of the kernel's events in the trace."""
import tracefile
import work


def read(run):
    t = tracefile.kernel_s(run.device, "muxq_gemm")
    w, p = run.work, run.peaks
    if not t or not w["quantized_ops"]:
        return None
    need = work.roofline_s(w["quantized_ops"], w["quantized_bytes"],
                           p["int8_ops_per_s"], p["hbm_bytes_per_s"])
    return 100.0 * need / t
