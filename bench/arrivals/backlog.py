"""A closed backlog: ``depth_buckets`` full buckets of requests stay
queued at every launch boundary, so every launch is a full bucket and the
device never waits for traffic."""


class Arrivals:
    closed = True

    def __init__(self, spec: dict):
        self.depth_buckets = int(spec["depth_buckets"])
        self.depth = 0

    def group_sizes(self, max_batch: int):
        return [max_batch]

    def start(self, t0: float, seconds: float, seed: int, max_batch: int):
        self.depth = self.depth_buckets * max_batch

    def release(self, now: float, sent: int, done: int):
        return [now] * max(0, self.depth - (sent - done))

    def owed(self, sent: int):
        return []
