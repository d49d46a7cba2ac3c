"""Mean wall time of one launch, GenServer.run_group to block_until_ready
(ServingMetrics.launches)."""


def read(run):
    return run.mean_launch_ms()
