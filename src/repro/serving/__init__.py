"""Async serving subsystem: continuous batching, deadlines, hot swap.

The serving layer between open-loop traffic and the jitted bucket
executables (see DESIGN.md "Serving scheduler"):

* :class:`ServeRequest` / :class:`RequestQueue` — requests carry
  ``(net, deadline, priority)``; arrivals are admitted by time, so a
  precomputed Poisson trace behaves like live traffic,
* :class:`ContinuousScheduler` — re-forms a pow2-bucket batch at every
  launch boundary (no drain-the-group), sheds requests whose deadline
  cannot be met (admission control from the :class:`ServiceEstimator`),
  and applies :meth:`~ContinuousScheduler.swap_checkpoint` between
  launches with a zero-recompile assertion,
* :class:`ServingMetrics` — p50/p95/p99 latency, goodput, shed rate,
  batch-occupancy histograms.

``repro.launch.serve_gen`` is the CLI over this package; the on-chip
benchmark (``bench/``) drives it with closed and open-loop traffic.
"""

from repro.serving.metrics import PERCENTILES, ServingMetrics, percentile
from repro.serving.queue import RequestQueue, ServeRequest
from repro.serving.scheduler import (ADMIT_SLACK, ContinuousScheduler,
                                     ServiceEstimator, VirtualClock,
                                     WallClock)

__all__ = [
    "ADMIT_SLACK", "PERCENTILES", "ContinuousScheduler", "RequestQueue",
    "ServeRequest", "ServiceEstimator", "ServingMetrics", "VirtualClock",
    "WallClock", "percentile",
]
