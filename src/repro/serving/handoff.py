"""Hand-off of rows between requests and one launch, in both directions.

**Inputs.**  A launch takes one padded batch whose leading axis holds
the group's rows.  Copying each request's row to the device on its own
(``jnp.asarray`` per row, then a device stack) costs a transfer and an
eager dispatch per request, ~0.5 ms each on a TPU v5e host.
:func:`stack_rows` instead stacks the rows into a fresh host buffer,
zeroes its pad rows, and copies the batch to the device in one call,
as row blocks of a few MB when it is larger.  A row that is already a
``jax.Array`` is read back to the host first, so callers that build
many rows should build them on the host.

**Outputs.**  A launch returns one array whose leading axis holds the
group's rows; each request keeps its own row.  Indexing the device
array with a Python int (``out[i]``) is one eager dispatch per request,
and each row index is a compiled program of its own, so a 256-row
launch paid 256 dispatches on the host while the device sat idle.
:func:`split_rows` instead runs one jitted program that returns every
row at once, cached by the output's shape and dtype.  The choice is
made from what the output is, never from a setting:

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
import jax.numpy as jnp
import numpy as np

SPLIT = "split"
INDEX = "index"

# A batch larger than this goes to the device as row blocks of about
# this size, copied side by side, then joined there.  On a TPU v5e a
# 25 MB batch took 11-12 ms to reach a program as one copy and ~7 ms as
# 2 MB blocks; under the profiler, ~350 ms against 40-86 ms.
CHUNK_BYTES = 2 << 20


def stack_rows(latents: Sequence[Any], bucket: int, dtype) -> jax.Array:
    """The ``bucket``-row batch of ``latents`` in ``dtype``, rows past
    ``len(latents)`` zero, built on the host and copied to the device in
    one call.  The host cast rounds to nearest even, as the device
    convert does, so the batch holds the bits a device stack would."""
    n = len(latents)
    rows = [np.asarray(z, dtype) for z in latents]
    # A fresh buffer per launch: the transfer may read it after
    # device_put returns.  No device argument keeps the placement
    # uncommitted on the default device.
    buf = np.empty((bucket, *rows[0].shape), dtype)
    np.stack(rows, out=buf[:n])
    buf[n:] = 0
    chunks = min(bucket, -(-buf.nbytes // CHUNK_BYTES))
    if chunks == 1:
        return jax.device_put(buf)
    return jnp.concatenate(jax.device_put(np.array_split(buf, chunks)))


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
