"""MB per launch that the model's skip joins write: the decoder inputs
it materialises by concatenating an encoder output ahead of the layer
below (the program's count, GenServer.join_bytes, from shapes at the
launch's bucket).  A kernel that read the two operands apart would not
write them.

The program keeps the count in its launch record
(ServingMetrics.launches, ``join_bytes``); read over the window's
launches, in traced runs, as host_outputs_ms is.  A program that keeps
no such count gives nothing to read."""


def read(run):
    window = run.window()
    if run.trace is None or not window or "join_bytes" not in window[0]:
        return None
    return sum(r["join_bytes"] for r in window) / len(window) / 1e6
