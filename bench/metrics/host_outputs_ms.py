"""Host time per launch in the scheduler's output hand-off (span
sched.outputs): the estimator update, the launch record and the
per-request loop (served records, the `out[i]` slices).

The program keeps each span's host ms in its launch record
(ServingMetrics.launches); read over the window's launches, in traced
runs, beside the device trace whose idle time it splits."""


def read(run):
    window = run.window()
    if run.trace is None or not window or "outputs_ms" not in window[0]:
        return None
    return sum(r["outputs_ms"] for r in window) / len(window)
