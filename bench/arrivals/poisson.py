"""Open-loop arrivals at ``rate_per_s``, sent on schedule whatever the
server does.  The count in a window is fixed, ``round(rate * seconds)``,
and the due times are that many sorted uniform draws over the window: a
Poisson process conditioned on its count, so every seed offers the same
work in another order."""

import numpy as np

# Requests are handed to the scheduler this long before they are due; they
# stay pending in its queue until their due time.
LOOKAHEAD_S = 0.002


class Arrivals:
    closed = False

    def __init__(self, spec: dict):
        self.rate_per_s = float(spec["rate_per_s"])
        self.due = np.zeros(0)

    def group_sizes(self, max_batch: int):
        return list(range(1, max_batch + 1))

    def offsets(self, seconds: float, seed: int) -> np.ndarray:
        """Sorted due times, in seconds from the window's start."""
        n = int(round(self.rate_per_s * seconds))
        rng = np.random.default_rng([seed, 0x7AFF1C])
        return np.sort(rng.uniform(0.0, seconds, n))

    def start(self, t0: float, seconds: float, seed: int, max_batch: int):
        self.due = t0 + self.offsets(seconds, seed)

    def release(self, now: float, sent: int, done: int):
        # What is due now, and the next request whenever the queue would
        # otherwise be empty, so that an idle step sleeps until it is due.
        end = sent
        while end < len(self.due) and (self.due[end] <= now + LOOKAHEAD_S
                                       or end == done):
            end += 1
        return self.due[sent:end].tolist()

    def owed(self, sent: int):
        return self.due[sent:].tolist()
