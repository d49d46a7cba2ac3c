"""Whole-step share of the chip's peak: the useful model FLOPs of the
images the window's launches made (2 x MACs, without the split-deconv
expansion) over the window's seconds, over the peak of the
configuration's dtype (float32 against the bf16 peak, so a float32 share
cannot come near 100%)."""


def read(run):
    if run.window_s <= 0 or not run.images:
        return None
    return 100.0 * run.model_flops() / run.window_s / run.peak_flops()
