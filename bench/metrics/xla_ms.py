"""Device time per launch of the serving cell's ops that are not Pallas
kernels: fc, conv, pads, relayouts (profiler trace)."""


def read(run):
    if run.trace is None or not run.window_launches:
        return None
    return 1e3 * run.trace["model_xla_s"] / run.window_launches
