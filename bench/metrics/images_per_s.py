"""Images of the launches made in the window over the window's seconds,
which run from its start to the end of its last launch (host clock)."""


def read(run):
    return run.images / run.window_s if run.window_s > 0 else None
