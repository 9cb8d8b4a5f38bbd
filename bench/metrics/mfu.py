"""Model operations per second of the traced window over the chip's
matmul peak for the configuration (int8 for quantized weights, bf16
otherwise): every projection site for the tokens that reach it,
attention over the live positions, the LM head for every sampled
position, as ``work.py`` counts them from the reference's description."""


def read(run):
    d, p = run.device, run.peaks
    if not d["window_s"] or not d["busy_s"]:
        return None
    peak = p["int8_ops_per_s"] if run.spec.get("quant") else p["bf16_flops_per_s"]
    return 100.0 * run.work["model_ops"] / d["window_s"] / peak
