"""Traffic.  A mix is a data file, ``traffic/<mix>.json``, that names its
arrival kind (``"arrival"``) and gives that kind's parameters.  An arrival
kind is a module, ``arrivals/<kind>.py``, found by name; its class
``Arrivals`` is the one interface the harness drives:

* ``Arrivals(spec)``: the kind's parameters from the mix file;
* ``closed``: True where the traffic waits on the server (a backlog): the
  requests attempted are those served in the window, and what is left
  queued at its close is dropped.  False for open-loop traffic: every
  request due in the window is attempted, and those still owed at the
  close are handed over and drained;
* ``group_sizes(max_batch)``: the launch sizes the traffic forms, which
  set-up compiles and runs once;
* ``start(t0, seconds, seed, max_batch)``: plans one window;
* ``release(now, sent, done)``: due times of the requests to hand the
  scheduler now, given how many were sent and how many finished;
* ``owed(sent)``: due times of the requests due in the window and not yet
  handed over when it closes.

Which configuration each request goes to, and its input, are the
harness's: inputs are taken in turn from a pool made at set-up, so that
making traffic costs the window nothing.
"""

from __future__ import annotations

import os

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))


def load(mix: str, spec: dict = None):
    """The arrivals of the mix ``traffic/<mix>.json`` (or of ``spec``)."""
    import harness
    if spec is None:
        spec = harness.read_json(BENCH, "traffic", mix + ".json")
    kind = spec.get("arrival", "")
    path = os.path.join(BENCH, "arrivals", kind + ".py")
    if not kind or not os.path.isfile(path):
        raise ValueError(f"traffic {mix!r}: no arrival kind {kind!r} "
                         f"in bench/arrivals/")
    return harness.load_module(path).Arrivals(spec)


def input_pool(shape, dist: str, size: int, seed: int) -> np.ndarray:
    """``size`` host inputs of ``shape`` (float32) drawn from the seed."""
    rng = np.random.default_rng([seed, 0x1A7E47])
    if dist == "normal":
        return rng.standard_normal((size, *shape), dtype=np.float32)
    if dist == "uniform":
        return rng.random((size, *shape), dtype=np.float32)
    raise ValueError(f"unknown input distribution {dist!r}")
