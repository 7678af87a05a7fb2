"""Share of its roofline that the PAM matmul kernels reach in training:
the least time the chip's HBM could move their operands and results
(bytes from the shapes of every pam_matmul and pam_exact_grad call in
the traced window, at bench/peaks.json's bytes/s) over their device
time. The chip has no sourced peak for the integer vector work a PAM
product is, so the bytes bound is the roofline."""

KERNELS = ("pam_matmul", "pam_exact_grad")


def read(run):
    t = run.trace_data
    if not t:
        return None
    ks = [t["kernels"][k] for k in KERNELS if k in t["kernels"]]
    secs = sum(k["seconds"] for k in ks)
    if secs <= 0:
        return None
    return 100.0 * sum(k["bytes"] for k in ks) / run.peaks["hbm_bytes_per_s"] / secs
