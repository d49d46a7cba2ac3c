"""The fused split-deconv kernels' share of their roofline: the least
time the chip could take for the window's kernel calls (per call, the
larger of executed split-deconv FLOPs over the peak and input, split
filter and output bytes over HBM bandwidth) over their traced device
time.  Float32 is measured against the bf16 peak."""
import flops


def read(run):
    if run.trace is None or run.trace["model_custom_s"] <= 0:
        return None
    least, _ = _least(run)
    return 100.0 * least / run.trace["model_custom_s"]


def note(run):
    """Which side bounds the least time: "compute" or "memory"."""
    return _least(run)[1] + "-bound"


def _least(run):
    return flops.least_seconds(run.sd_kernel_calls(), run.peak_flops(),
                               run.peak["hbm_bytes_per_s"])
