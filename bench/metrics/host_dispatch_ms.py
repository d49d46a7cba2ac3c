"""Host time per launch in the dispatch of GenServer.run_group (span
serve.dispatch): the compiled cell's call and the crop, enqueued and not
waited on.

The program keeps each span's host ms in its launch record
(ServingMetrics.launches); read over the window's launches, in traced
runs, beside the device trace whose idle time it splits."""


def read(run):
    window = run.window()
    if run.trace is None or not window or "dispatch_ms" not in window[0]:
        return None
    return sum(r["dispatch_ms"] for r in window) / len(window)
