"""Hand-off of one launch's batched output to its requests.

A launch returns one array whose leading axis holds the group's rows;
each request keeps its own row.  Indexing the device array with a
Python int (``out[i]``) is one eager dispatch per request, and each row
index is a compiled program of its own, so a 256-row launch paid 256
dispatches on the host while the device sat idle.  :func:`split_rows`
instead runs one jitted program that returns every row at once, cached
by the output's shape and dtype.

The choice is made from what the output is, never from a setting:

* a ``jax.Array`` on one device takes the one-program path
  (``"split"``);
* an array sharded over several devices (a ``--dp``/``--mp`` mesh) and
  anything that is not a ``jax.Array`` (numpy arrays and lists from
  test stubs) keeps per-row indexing (``"index"``).
"""

from __future__ import annotations

import functools
from typing import Any, Sequence, Tuple

import jax

SPLIT = "split"
INDEX = "index"


@functools.partial(jax.jit, static_argnums=1)
def handoff_rows(y: jax.Array, n: int) -> Tuple[jax.Array, ...]:
    """The first ``n`` rows of ``y`` as ``n`` arrays, in one program."""
    return tuple(y[i] for i in range(n))


def split_rows(out: Any, n: int) -> Tuple[Sequence[Any], str]:
    """``n`` per-request results of one launch's output ``out`` (the
    values, dtypes and shapes of ``out[i]``), and the path taken:
    ``"split"`` or ``"index"`` (see the module doc)."""
    if isinstance(out, jax.Array) and len(out.sharding.device_set) == 1:
        return handoff_rows(out, n), SPLIT
    return [out[i] for i in range(n)], INDEX
