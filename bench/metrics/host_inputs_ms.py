"""Host time per launch in the input copies of GenServer.run_group (span
serve.inputs): per-request jnp.asarray, the stack and the pad.

The program keeps each span's host ms in its launch record
(ServingMetrics.launches); read over the window's launches, in traced
runs, beside the device trace whose idle time it splits."""


def read(run):
    window = run.window()
    if run.trace is None or not window or "inputs_ms" not in window[0]:
        return None
    return sum(r["inputs_ms"] for r in window) / len(window)
