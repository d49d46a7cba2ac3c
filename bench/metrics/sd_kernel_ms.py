"""Device time per launch of the serving cell's Pallas custom calls, on
this path the fused split-deconv kernels (profiler trace)."""


def read(run):
    if run.trace is None or not run.window_launches:
        return None
    if run.trace["model_custom_s"] <= 0:
        return None
    return 1e3 * run.trace["model_custom_s"] / run.window_launches
