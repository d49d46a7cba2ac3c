"""Async serving subsystem (repro.serving): queue/priority semantics,
starvation-bounded batching, scheduler invariants (nothing lost or
double-served, deadline shedding, closed compile-shape set), live
checkpoint hot-swap with zero recompiles, and the row hand-off in both
directions."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.accounting import NetworkSpec
from repro.launch.batching import pow2_bucket, take_group
from repro.launch.serve_gen import GenServer, reduced_spec, serve_async
from repro.models.generative import GenerativeModel
from repro.serving import (ContinuousScheduler, RequestQueue,
                           ServeRequest, ServiceEstimator, ServingMetrics,
                           VirtualClock, percentile)
from repro.serving import handoff
from repro.serving import scheduler as scheduler_mod
from repro.serving.handoff import handoff_rows, split_rows, stack_rows

SPEC = reduced_spec()


def _server(**kw):
    kw.setdefault("nets", ["g"])
    kw.setdefault("specs", {"g": SPEC})
    return GenServer(**kw)


# ---------------------------------------------------------------------------
# Metrics
# ---------------------------------------------------------------------------

def test_percentile():
    assert percentile([], 50) is None
    assert percentile([7.0], 99) == 7.0
    vals = list(map(float, range(1, 101)))
    assert percentile(vals, 50) == pytest.approx(50.5)
    assert percentile(vals, 95) == pytest.approx(95.05)
    assert percentile(vals, 99) == pytest.approx(99.01)


def test_metrics_summary_counts():
    m = ServingMetrics()
    m.record_served(0, "a", 0.010, True)
    m.record_served(1, "a", 0.030, True)
    m.record_served(2, "b", 0.050, False)      # late completion
    m.record_shed(3, "b", "expired")
    m.record_launch("a", 4, 2, 5.0)
    m.record_launch("b", 1, 1, 5.0)
    s = m.summary(wall_s=1.0)
    assert s["served"] == 3 and s["shed"] == 1
    assert s["served_on_time"] == 2 and s["goodput_rps"] == 2.0
    assert s["shed_rate"] == 0.25
    assert s["goodput_ratio"] == 0.5
    assert s["latency_ms"]["p50"] == pytest.approx(30.0)
    assert s["shed_reasons"] == {"expired": 1}
    assert s["occupancy_hist"] == {"4": {"2": 1}, "1": {"1": 1}}
    assert s["mean_occupancy"] == pytest.approx(3 / 5)
    assert set(s["latency_ms_per_net"]) == {"a", "b"}


# ---------------------------------------------------------------------------
# Request queue: arrival gating + (priority, arrival, rid) ordering
# ---------------------------------------------------------------------------

def test_queue_poll_respects_arrival_times():
    q = RequestQueue()
    for rid, t in [(0, 0.5), (1, 0.1), (2, 2.0)]:
        q.push(ServeRequest(rid=rid, net="g", latent=None, arrival_t=t))
    assert len(q) == 0 and q.pending_count() == 3
    assert q.next_arrival() == 0.1
    q.poll(0.6)
    assert [r.rid for r in q.live] == [1, 0]   # arrival order, not push
    assert q.next_arrival() == 2.0
    q.poll(5.0)
    assert [r.rid for r in q.live] == [1, 0, 2]
    assert q.next_arrival() is None


def test_queue_priority_orders_live():
    q = RequestQueue()
    q.push(ServeRequest(rid=0, net="g", latent=None, arrival_t=0.0))
    q.push(ServeRequest(rid=1, net="g", latent=None, arrival_t=1.0,
                        priority=-1))              # urgent, arrives later
    q.push(ServeRequest(rid=2, net="g", latent=None, arrival_t=0.5))
    q.poll(10.0)
    assert [r.rid for r in q.live] == [1, 0, 2]    # priority, then FIFO


# ---------------------------------------------------------------------------
# Starvation-bounded take_group (the head-of-line fix)
# ---------------------------------------------------------------------------

def test_take_group_full_bucket_bypasses_cold_head():
    """Regression: one cold-net request at the head used to force a
    1-of-N launch while a hot net had a full bucket waiting."""
    q = [(0, "cold")] + [(i, "hot") for i in range(1, 9)]
    skips = {}
    group, rest = take_group(q, lambda r: r[1], 4,
                             skip_counts=skips, max_skips=2)
    assert [r[1] for r in group] == ["hot"] * 4    # full bucket first
    assert group == [(1, "hot"), (2, "hot"), (3, "hot"), (4, "hot")]
    assert rest[0] == (0, "cold") and skips == {"cold": 1}


def test_take_group_starvation_bound_is_hard():
    """After max_skips bypasses the cold head launches next, however
    much hot traffic is queued — and its skip count resets."""
    q = [(0, "cold")] + [(i, "hot") for i in range(1, 40)]
    skips = {}
    launches = []
    while q:
        group, q = take_group(q, lambda r: r[1], 4,
                              skip_counts=skips, max_skips=2)
        launches.append([r[1] for r in group])
    assert launches[0] == ["hot"] * 4
    assert launches[1] == ["hot"] * 4
    assert launches[2] == ["cold"]                 # bound hit: served
    assert "cold" not in skips                     # reset on service
    assert all(k == "hot" for g in launches[3:] for k in g)


def test_take_group_no_bypass_without_full_bucket():
    """A bigger-but-not-full rival never bypasses the head."""
    q = [(0, "a"), (1, "b"), (2, "b"), (3, "b")]
    group, rest = take_group(q, lambda r: r[1], 4,
                             skip_counts={}, max_skips=3)
    assert group == [(0, "a")]


def test_take_group_default_behaviour_unchanged():
    """max_skips=0 (every existing call site) keeps strict head-of-line
    FIFO semantics."""
    q = [(0, "cold")] + [(i, "hot") for i in range(1, 9)]
    group, rest = take_group(q, lambda r: r[1], 4)
    assert group == [(0, "cold")]
    assert rest == [(i, "hot") for i in range(1, 9)]


# ---------------------------------------------------------------------------
# Scheduler invariants on a stub server + virtual clock
# ---------------------------------------------------------------------------

class StubServer:
    """Minimal server surface; launches are simulated on the clock."""

    def __init__(self, clock, max_batch=4, service_s=1.0):
        self.clock = clock
        self.max_batch = max_batch
        self.service_s = service_s
        self.launched = []          # (net, [rids]) per launch

    def bucket(self, n):
        return pow2_bucket(n, self.max_batch)

    def swap_checkpoint(self, net, params):
        pass


def _stub_sched(clock=None, max_batch=4, service_s=1.0, est_ms=None,
                **kw):
    clock = clock or VirtualClock()
    server = StubServer(clock, max_batch=max_batch, service_s=service_s)

    def launch(net, latents, bucket):
        server.launched.append((net, list(latents)))
        clock.advance(server.service_s)
        return None

    est = (ServiceEstimator(seed_fn=lambda n, b: est_ms)
           if est_ms is not None else ServiceEstimator())
    sched = ContinuousScheduler(server, clock=clock, launch_fn=launch,
                                collect_outputs=False, estimator=est,
                                **kw)
    return sched, server, clock


def test_scheduler_nothing_lost_or_double_served():
    """Every submitted rid ends in exactly one of served/shed."""
    sched, server, clock = _stub_sched(service_s=0.3)
    rng = np.random.RandomState(0)
    t = 0.0
    for rid in range(40):
        t += float(rng.exponential(0.1))
        sched.submit("n%d" % (rid % 3), rid, rid=rid, arrival_t=t,
                     deadline_ms=10_000.0)
    sched.run()
    served = [r["rid"] for r in sched.metrics.served]
    shed = [r["rid"] for r in sched.metrics.shed]
    assert sorted(served + shed) == list(range(40))
    assert len(set(served)) == len(served)
    launched = [rid for _, rids in server.launched for rid in rids]
    assert sorted(launched) == sorted(served)


def test_scheduler_continuous_batching_admits_new_arrivals():
    """A request arriving while an earlier launch runs rides the very
    next launch — it does not wait for the original queue to drain."""
    sched, server, clock = _stub_sched(max_batch=2, service_s=1.0)
    for rid in range(4):                    # two full launches queued
        sched.submit("g", rid, rid=rid, arrival_t=0.0)
    sched.submit("g", 9, rid=9, arrival_t=1.5)   # lands mid-traffic
    sched.run()
    assert [sorted(r) for _, r in server.launched] == [[0, 1], [2, 3],
                                                       [9]]
    # the late arrival's latency is its own service, not the backlog's
    lat = {r["rid"]: r["latency_ms"] for r in sched.metrics.served}
    assert lat[9] == pytest.approx(1500.0)  # 0.5s wait + 1s service


def test_scheduler_sheds_expired_not_served():
    """A request whose deadline passed while it queued is shed, never
    launched."""
    sched, server, clock = _stub_sched(max_batch=4, service_s=1.0)
    for rid in range(4):                    # full bucket of hot traffic
        sched.submit("hot", rid, rid=rid, arrival_t=0.0)
    # behind it: a request that dies at t=0.5 (launch takes 1s)
    sched.submit("cold", 7, rid=7, arrival_t=0.0, deadline_ms=500.0)
    sched.run()
    assert [r["rid"] for r in sched.metrics.shed] == [7]
    assert sched.metrics.shed[0]["reason"] == "expired"
    assert all(7 not in rids for _, rids in server.launched)


def test_scheduler_sheds_unmeetable_by_estimate():
    """Admission control: with a seeded 1000ms estimate, a 200ms
    deadline is shed up front; a 10s deadline is served."""
    sched, server, clock = _stub_sched(service_s=1.0, est_ms=1000.0)
    sched.submit("g", 0, rid=0, arrival_t=0.0, deadline_ms=200.0)
    sched.submit("g", 1, rid=1, arrival_t=0.0, deadline_ms=10_000.0)
    sched.run()
    assert [r["rid"] for r in sched.metrics.shed] == [0]
    assert sched.metrics.shed[0]["reason"] == "unmeetable"
    assert [r["rid"] for r in sched.metrics.served] == [1]
    assert sched.metrics.served[0]["on_time"]


def test_scheduler_estimator_ewma_takes_over():
    sched, server, clock = _stub_sched(service_s=2.0, est_ms=1.0)
    assert sched.estimator.estimate_ms("g", 1) == 1.0     # seed
    sched.submit("g", 0, rid=0, arrival_t=0.0)
    sched.run()
    assert sched.estimator.estimate_ms("g", 1) == pytest.approx(2000.0)


def test_scheduler_starvation_bound_under_hot_flood():
    """The cold net is bypassed by full hot buckets at most max_skips
    times, then launches — even with hot traffic still queued."""
    sched, server, clock = _stub_sched(max_batch=4, max_skips=2,
                                       service_s=0.1)
    sched.submit("cold", 0, rid=0, arrival_t=0.0)
    for rid in range(1, 17):
        sched.submit("hot", rid, rid=rid, arrival_t=0.0)
    sched.run()
    kinds = [net for net, _ in server.launched]
    assert kinds.index("cold") == 2         # exactly after 2 bypasses
    assert kinds.count("hot") == 4


def test_scheduler_priority_request_jumps_queue():
    sched, server, clock = _stub_sched(max_batch=2, service_s=1.0)
    sched.submit("a", 0, rid=0, arrival_t=0.0)
    sched.submit("b", 1, rid=1, arrival_t=0.0)
    sched.submit("b", 2, rid=2, arrival_t=0.0, priority=-5)
    sched.run()
    # the urgent "b" heads the live queue, so net b launches first
    assert server.launched[0][0] == "b"
    assert 2 in server.launched[0][1]


def test_scheduler_duplicate_rid_rejected():
    sched, _, _ = _stub_sched()
    sched.submit("g", 0, rid=3, arrival_t=0.0)
    with pytest.raises(ValueError, match="duplicate"):
        sched.submit("g", 0, rid=3, arrival_t=0.0)


# ---------------------------------------------------------------------------
# Scheduler on the real server: compile-set closure + hot swap
# ---------------------------------------------------------------------------

def test_scheduler_compile_shape_set_stays_closed():
    """Whatever request counts arrive, the compiled cells stay within
    the pow2 bucket ladder and repeat traffic never retraces."""
    server = _server(max_batch=8)
    sched = ContinuousScheduler(server)
    for n in (3, 5, 1, 8, 2, 7):
        z = jax.random.normal(jax.random.PRNGKey(n), (n, 16))
        for i in range(n):
            sched.submit("g", z[i])
    sched.run()
    ladder = set(server.buckets())
    assert {k[1] for k in server._compiled} <= ladder
    count = server.compile_count
    # replay: same buckets, zero new traces (asserted by the scheduler
    # itself too — a retrace of an existing cell raises)
    for i in range(5):
        sched.submit("g", jax.random.normal(jax.random.PRNGKey(99 + i),
                                            (16,)))
    sched.run()
    assert server.compile_count == count


def test_hot_swap_zero_recompiles_and_never_mixed():
    """swap_checkpoint mid-traffic: every launch serves entirely-old or
    entirely-new weights (never a mix), and the swap triggers zero
    recompiles (params/plans are jit arguments of the compiled cell)."""
    server = _server(max_batch=4)
    _, params_a = server.model("g")
    params_b = GenerativeModel(SPEC, "native").init(jax.random.PRNGKey(7))
    ref = GenerativeModel(SPEC, "native")

    sched = ContinuousScheduler(server)
    z1 = jax.random.normal(jax.random.PRNGKey(1), (4, 16))
    for i in range(4):
        sched.submit("g", z1[i], rid=i)
    while not sched.metrics.launches:       # drive to the first launch
        assert sched.step()
    compiles_before = server.compile_count
    assert compiles_before >= 1

    sched.swap_checkpoint("g", params_b)    # applied at next boundary
    z2 = jax.random.normal(jax.random.PRNGKey(2), (4, 16))
    for i in range(4):
        sched.submit("g", z2[i], rid=10 + i)
    sched.run()

    assert server.compile_count == compiles_before   # ZERO recompiles
    assert sched.swaps_applied == 1
    ref_a = np.asarray(ref.apply(params_a, z1))
    ref_b_old = np.asarray(ref.apply(params_a, z2))
    ref_b_new = np.asarray(ref.apply(params_b, z2))
    for i in range(4):      # pre-swap launch: old weights exactly
        np.testing.assert_allclose(np.asarray(sched.results[i]),
                                   ref_a[i], rtol=1e-4, atol=1e-4)
    post = np.stack([np.asarray(sched.results[10 + i])
                     for i in range(4)])
    # post-swap launch: new weights on every row — and demonstrably NOT
    # the old ones (the two checkpoints disagree on these inputs)
    assert not np.allclose(post, ref_b_old, rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(post, ref_b_new, rtol=1e-4, atol=1e-4)


def _host_spans(trace_dir, names):
    """(start, end, name, stats) of each host event named in ``names`` in
    the profile under ``trace_dir``, parents before their children."""
    import glob
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    data = jax.profiler.ProfileData.from_file(path)
    out = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name,
            dict(ev.stats))
           for plane in data.planes for line in plane.lines
           for ev in line.events if ev.name in names]
    return sorted(out, key=lambda s: (s[0], -s[1]))


STEP_SPANS = ("sched.admit", "sched.launch", "serve.inputs",
              "serve.dispatch", "sched.block", "sched.outputs")


def test_step_spans_split_a_launch(tmp_path):
    """One step under the profiler: sched.launch encloses serve.inputs,
    serve.dispatch and sched.block in that order, carries its stats, and
    sched.outputs follows it; the launch record keeps each phase's ms."""
    server = _server(max_batch=4)
    server.warmup()
    sched = ContinuousScheduler(server)
    z = jax.random.normal(jax.random.PRNGKey(3), (3, 16))
    for i in range(3):
        sched.submit("g", z[i], rid=i)
    with jax.profiler.trace(str(tmp_path)):
        assert sched.step()
    spans = _host_spans(str(tmp_path), STEP_SPANS + ("sched.wait",))
    assert [s[2] for s in spans] == list(STEP_SPANS)
    (_, admit_end, _, _), launch, *inner, outputs = spans
    assert admit_end <= launch[0]
    t = launch[0]
    for a, b, name, _ in inner:
        assert t <= a <= b <= launch[1], name
        t = b
    assert launch[1] <= outputs[0]
    stats = launch[3]
    assert (int(stats["launch"]), stats["net"], int(stats["bucket"]),
            int(stats["n"])) == (0, "g", 4, 3)
    rec = sched.metrics.launches[0]
    assert {"inputs_ms", "dispatch_ms", "outputs_ms"} <= set(rec)
    assert rec["inputs_ms"] + rec["dispatch_ms"] <= rec["ms"]
    assert len(sched.results) == 3


def test_wait_span_covers_the_sleep_to_the_next_arrival(tmp_path):
    """With nothing live the step sleeps to the next arrival inside
    sched.wait, after sched.admit, and launches nothing."""
    sched, server, clock = _stub_sched()
    sched.submit("n0", 0, rid=0, arrival_t=5.0)
    with jax.profiler.trace(str(tmp_path)):
        assert sched.step()
    spans = _host_spans(str(tmp_path), STEP_SPANS + ("sched.wait",))
    assert [s[2] for s in spans] == ["sched.admit", "sched.wait"]
    assert clock.now() == 5.0 and not server.launched


def test_server_swap_checkpoint_rebinds_engine():
    server = _server(max_batch=4)
    model, params_a = server.model("g")
    params_b = GenerativeModel(SPEC, "native").init(jax.random.PRNGKey(3))
    server.swap_checkpoint("g", params_b)
    m2, p2 = server.model("g")
    assert m2 is model and p2 is params_b
    assert model.engine.bound_to(params_b)
    assert not model.engine.bound_to(params_a)


def test_serve_async_matches_run_group():
    """The scheduler hands each request exactly the row the model step
    computes for it: per net, a full bucket and a cropped one (3 rows
    padded to 4), ``serve_async`` equals ``run_group`` on the same
    rows bit for bit."""
    spec_b = NetworkSpec("g2", list(SPEC.layers))
    server = GenServer(nets=["g", "g2"], specs={"g": SPEC, "g2": spec_b},
                       max_batch=4)
    reqs = []
    for net, n, seed in (("g", 4, 1), ("g2", 3, 2)):
        for r in server.random_requests(net, n, seed=seed):
            r.rid = len(reqs)
            reqs.append(r)
    results, stats = serve_async(server, reqs)
    assert stats["served"] == 7 and stats["launches"] == 2
    for net in ("g", "g2"):
        group = [r for r in reqs if r.net == net]
        ref = np.asarray(server.run_group(net, [r.latent for r in group]))
        assert ref.shape[0] == len(group)
        for i, r in enumerate(group):
            np.testing.assert_array_equal(np.asarray(results[r.rid]),
                                          ref[i], err_msg=net)


# ---------------------------------------------------------------------------
# Input hand-off: host rows stacked on the host, one transfer per launch
# ---------------------------------------------------------------------------

def _device_stack(latents, bucket, dtype):
    """The batch as run_group built it before stack_rows: per-row
    jnp.asarray, a device stack and a zero pad."""
    x = jnp.stack([jnp.asarray(z, dtype) for z in latents])
    if bucket > len(latents):
        pad = jnp.zeros((bucket - len(latents), *x.shape[1:]), dtype)
        x = jnp.concatenate([x, pad])
    return x


def _rows(n, seed=0):
    """``n`` float32 host rows; the first holds bfloat16 rounding ties
    (low 16 bits 0x8000, odd and even above them) and signed zeros."""
    rng = np.random.RandomState(seed)
    rows = [rng.standard_normal((5, 4, 3)).astype(np.float32)
            for _ in range(n)]
    high = (rng.randint(0x3C00, 0x4400, size=rows[0].size, dtype=np.uint32)
            | rng.randint(0, 2, size=rows[0].size, dtype=np.uint32) << 15)
    ties = ((high << 16) | 0x8000).view(np.float32).reshape(rows[0].shape)
    ties[0, 0, :2] = (0.0, -0.0)
    rows[0] = ties
    return rows


def _bits(x):
    return np.asarray(x).view(np.uint8)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("bucket,n", [(8, 8), (8, 3)])
def test_stack_rows_host_path_matches_device_stack(dtype, bucket, n):
    """Host rows take one host stack and one transfer; the batch is the
    old device stack/pad bit for bit (the host bfloat16 cast rounds to
    nearest even, as the device convert does), pad rows exact zeros,
    placed uncommitted on the default device as the old batch was."""
    dtype = jnp.dtype(dtype)
    rows = _rows(n)
    x = stack_rows(rows, bucket, dtype)
    assert isinstance(x, jax.Array) and not x.committed
    assert x.devices() == {jax.devices()[0]}
    assert x.dtype == dtype and x.shape == (bucket, 5, 4, 3)
    old = _device_stack(rows, bucket, dtype)
    np.testing.assert_array_equal(_bits(x), _bits(old))
    converted = jnp.stack([jnp.asarray(z).astype(dtype) for z in rows])
    np.testing.assert_array_equal(_bits(x[:n]), _bits(converted))
    assert not np.asarray(x[n:], np.float32).any()
    assert not _bits(x[n:]).any()                   # +0.0, not -0.0


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("bucket,n", [(8, 8), (8, 3)])
def test_stack_rows_chunked_copy_matches_device_stack(dtype, bucket, n,
                                                      monkeypatch):
    """A batch over CHUNK_BYTES goes over in row blocks joined on the
    device; the batch is still the old device stack/pad bit for bit,
    one uncommitted array on the default device."""
    dtype = jnp.dtype(dtype)
    rows = _rows(n)
    row_bytes = rows[0].size * dtype.itemsize
    monkeypatch.setattr(handoff, "CHUNK_BYTES", 3 * row_bytes)
    x = stack_rows(rows, bucket, dtype)
    assert isinstance(x, jax.Array) and not x.committed
    assert x.devices() == {jax.devices()[0]}
    assert x.dtype == dtype and x.shape == (bucket, 5, 4, 3)
    np.testing.assert_array_equal(_bits(x),
                                  _bits(_device_stack(rows, bucket, dtype)))
    assert not _bits(x[n:]).any()


@pytest.mark.parametrize("kind", ["device", "mixed"])
def test_stack_rows_device_rows_match_device_stack(kind):
    """Rows that are jax.Arrays (rows of a device batch, alone or mixed
    with host rows) are read back and stacked on the host, and give the
    old device stack/pad's values bit for bit."""
    z = jax.random.normal(jax.random.PRNGKey(3), (3, 5, 4, 3))
    rows = [z[i] for i in range(3)]
    if kind == "mixed":
        rows[1] = np.asarray(rows[1])
    for dtype in (jnp.dtype("float32"), jnp.dtype("bfloat16")):
        x = stack_rows(rows, 4, dtype)
        assert x.dtype == dtype and x.shape == (4, 5, 4, 3)
        np.testing.assert_array_equal(_bits(x),
                                      _bits(_device_stack(rows, 4, dtype)))


@pytest.mark.parametrize("path", ["host", "device", "chunked"])
def test_run_group_repeat_launch_adds_no_compile(path, monkeypatch):
    """A second launch of one shape, from host rows, from device rows
    or copied in row blocks, retraces no cell and compiles no program,
    and gives the first's outputs."""
    if path == "chunked":
        monkeypatch.setattr(handoff, "CHUNK_BYTES", 64)
    server = _server(max_batch=4)
    z = jax.random.normal(jax.random.PRNGKey(8), (3, 16))
    rows = ([z[i] for i in range(3)] if path == "device"
            else [np.asarray(z[i]) for i in range(3)])
    first = jax.block_until_ready(server.run_group("g", rows))
    count = server.compile_count
    events = []

    def listen(event, duration, **kw):
        if event.startswith("/jax/core/compile/"):
            events.append(event)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        again = jax.block_until_ready(server.run_group("g", rows))
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert server.compile_count == count
    assert events == []
    np.testing.assert_array_equal(np.asarray(again), np.asarray(first))


def test_scheduler_serves_host_and_device_latents_alike():
    """The scheduler serves numpy latents and jax.Array latents of the
    same values to the same outputs, one launch each."""
    server = _server(max_batch=4)
    z = jax.random.normal(jax.random.PRNGKey(6), (4, 16))
    sched = ContinuousScheduler(server)
    for i in range(4):
        sched.submit("g", np.asarray(z[i]), rid=i)
    sched.run()
    for i in range(4):
        sched.submit("g", z[i], rid=10 + i)
    sched.run()
    assert [r["n"] for r in sched.metrics.launches] == [4, 4]
    assert sched.stats(1.0)["handoff"] == {"split": 2, "index": 0}
    for i in range(4):
        np.testing.assert_array_equal(np.asarray(sched.results[i]),
                                      np.asarray(sched.results[10 + i]))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_random_requests_draws_host_rows(dtype):
    """random_requests hands out host rows, so run_group never reads a
    row back from the device, holding the draw it always made."""
    server = _server(max_batch=4, dtype=dtype)
    reqs = server.random_requests("g", 3, seed=4)
    want = jax.random.normal(jax.random.PRNGKey(4), (3, 16),
                             jnp.dtype(dtype))
    for i, r in enumerate(reqs):
        assert isinstance(r.latent, np.ndarray)
        assert r.latent.dtype == jnp.dtype(dtype)
        np.testing.assert_array_equal(_bits(r.latent), _bits(want[i]))


_HOST_INPUTS_2DEV = """
import numpy as np, jax
from repro.launch.serve_gen import GenServer, reduced_spec
from repro.serving import ContinuousScheduler
assert jax.device_count() == 2
server = GenServer(nets=["g"], specs={"g": reduced_spec()}, max_batch=4,
                   dp=2)
z = jax.random.normal(jax.random.PRNGKey(9), (3, 16))
ref = server.run_group("g", [z[i] for i in range(3)])
count = server.compile_count
sched = ContinuousScheduler(server)
for i in range(3):
    sched.submit("g", np.asarray(z[i]), rid=i)
sched.run()
assert len(sched.metrics.launches) == 1
assert server.compile_count == count
for i in range(3):
    assert (np.asarray(sched.results[i]) == np.asarray(ref[i])).all()
print("HOST_INPUTS_OK")
"""


def test_stack_rows_host_inputs_on_dp2_mesh(multi_device_run):
    """A --dp 2 server on two devices, fed numpy latents, runs the same
    compiled cell and matches its device-latent result."""
    assert "HOST_INPUTS_OK" in multi_device_run(_HOST_INPUTS_2DEV, ndev=2)


# ---------------------------------------------------------------------------
# Output hand-off: one program splits a launch's output into its rows
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bucket,n", [(8, 8), (8, 3)])
def test_split_rows_match_index(bucket, n):
    """Full bucket and a cropped group: one program's rows are the
    dtypes, shapes and bits of out[i], and stay device arrays."""
    y = jax.random.normal(jax.random.PRNGKey(bucket + n), (bucket, 5, 4, 3))
    for out in (y, y[:n]):          # launch output before and after crop
        parts, path = split_rows(out, n)
        assert path == "split" and len(parts) == n
        for i, part in enumerate(parts):
            assert isinstance(part, jax.Array)
            assert part.dtype == out[i].dtype
            assert part.shape == out[i].shape
            np.testing.assert_array_equal(np.asarray(part),
                                          np.asarray(out[i]))


@pytest.mark.parametrize("kind", ["numpy", "list"])
def test_split_rows_index_path_for_host_outputs(kind):
    """What is not a jax.Array (a stub launch_fn's numpy array or list)
    is indexed row by row, with the same values."""
    y = np.arange(24, dtype=np.float32).reshape(4, 3, 2)
    out = y if kind == "numpy" else [row.copy() for row in y]
    parts, path = split_rows(out, 3)
    assert path == "index" and len(parts) == 3
    for i, part in enumerate(parts):
        np.testing.assert_array_equal(part, y[i])


def test_split_rows_second_launch_adds_no_compile():
    """The split program is cached by shape and dtype: a second launch
    of the same bucket traces and compiles nothing."""
    events = []

    def listen(event, duration, **kw):
        if event.startswith("/jax/core/compile/"):
            events.append(event)

    y = jax.random.normal(jax.random.PRNGKey(0), (16, 6, 2))
    jax.block_until_ready(split_rows(y, 16)[0])      # the first launch
    size = handoff_rows._cache_size()
    again = jax.block_until_ready(y + 1.0)           # the second's output
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        parts, path = split_rows(again, 16)
        jax.block_until_ready(parts)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert path == "split"
    assert handoff_rows._cache_size() == size
    assert events == []


_SHARDED_HANDOFF_2DEV = """
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_dev_mesh
from repro.launch.serve_gen import GenServer, reduced_spec
from repro.serving import ContinuousScheduler
from repro.serving.handoff import split_rows
assert jax.device_count() == 2
mesh = make_dev_mesh(2, 1)
y = jax.device_put(jnp.arange(48.0).reshape(4, 3, 4),
                   NamedSharding(mesh, P("data")))
parts, path = split_rows(y, 4)
assert path == "index", path
for i, part in enumerate(parts):
    assert isinstance(part, jax.Array)
    assert (np.asarray(part) == np.asarray(y[i])).all()
server = GenServer(nets=["g"], specs={"g": reduced_spec()}, max_batch=4,
                   dp=2)
z = jax.random.normal(jax.random.PRNGKey(5), (4, 16))
sched = ContinuousScheduler(server)
for i in range(4):
    sched.submit("g", z[i], rid=i)
sched.run()
ref = server.run_group("g", [z[i] for i in range(4)])
assert len(ref.sharding.device_set) == 2
assert [r["handoff"] for r in sched.metrics.launches] == ["index"]
assert sched.stats(1.0)["handoff"] == {"split": 0, "index": 1}
for i in range(4):
    assert (np.asarray(sched.results[i]) == np.asarray(ref[i])).all()
print("HANDOFF_OK")
"""


def test_split_rows_sharded_output_takes_index_path(multi_device_run):
    """A batch-sharded output (a --dp 2 server on two devices) keeps
    per-row indexing, with the values out[i] gives."""
    assert "HANDOFF_OK" in multi_device_run(_SHARDED_HANDOFF_2DEV, ndev=2)


def test_scheduler_reports_handoff_paths(monkeypatch):
    """Launch records and stats() name the hand-off path: "split" for a
    real server's device output, "index" for a stub's numpy output, and
    nothing at all with collect_outputs=False."""
    server = _server(max_batch=4)
    sched = ContinuousScheduler(server)
    z = jax.random.normal(jax.random.PRNGKey(4), (6, 16))
    for i in range(6):
        sched.submit("g", z[i], rid=i)
    sched.run()
    assert [r["handoff"] for r in sched.metrics.launches] == ["split"] * 2
    assert sched.stats(1.0)["handoff"] == {"split": 2, "index": 0}
    assert all(isinstance(sched.results[i], jax.Array) for i in range(6))

    clock = VirtualClock()
    stub = StubServer(clock, max_batch=4)
    host = ContinuousScheduler(
        stub, clock=clock,
        launch_fn=lambda net, latents, bucket: np.asarray(latents) * 2.0)
    for i in range(3):
        host.submit("g", float(i), rid=i, arrival_t=0.0)
    host.run()
    assert host.stats(1.0)["handoff"] == {"split": 0, "index": 1}
    assert [host.results[i] for i in range(3)] == [0.0, 2.0, 4.0]

    def refuse(out, n):
        raise AssertionError("hand-off ran with collect_outputs=False")

    monkeypatch.setattr(scheduler_mod, "split_rows", refuse)
    quiet = ContinuousScheduler(server, collect_outputs=False)
    for i in range(3):
        quiet.submit("g", z[i], rid=i)
    quiet.run()
    assert quiet.results == {}
    assert [r["handoff"] for r in quiet.metrics.launches] == [None]
    assert quiet.stats(1.0)["handoff"] == {"split": 0, "index": 0}


# ---------------------------------------------------------------------------
# Service-time estimates from the autotune plan cache
# ---------------------------------------------------------------------------

def test_engine_estimate_ms_from_measured_plans(tmp_path, monkeypatch):
    from repro.engine import SDEngine
    eng = SDEngine(SPEC)
    layers = [l for l in SPEC.layers if l.kind == "deconv"]
    plans = {}
    for ms, layer in zip((0.5, 0.7), layers):
        geom = eng.layer_geom(layer, 4)
        plans[geom.key()] = {"th": 1, "tcin": 1, "tcout": 1, "ms": ms,
                             "source": "measured",
                             "backend": jax.default_backend()}
    cache = tmp_path / "plans.json"
    cache.write_text(json.dumps({"version": 1, "plans": plans}))
    monkeypatch.setenv("REPRO_SD_PLAN_CACHE", str(cache))

    params = GenerativeModel(SPEC, "native").init(jax.random.PRNGKey(0))
    eng.bind(params)
    assert eng.estimate_ms(4) == pytest.approx(1.2)
    assert eng.estimate_ms(2) is None       # batch 2: nothing measured


def test_scheduler_seeds_estimator_from_engine(tmp_path, monkeypatch):
    server = _server(max_batch=4)
    model, _ = server.model("g")
    layers = [l for l in SPEC.layers if l.kind == "deconv"]
    plans = {}
    for ms, layer in zip((1.5, 2.5), layers):
        geom = model.engine.layer_geom(layer, 4)
        plans[geom.key()] = {"th": 1, "tcin": 1, "tcout": 1, "ms": ms,
                             "source": "measured",
                             "backend": jax.default_backend()}
    cache = tmp_path / "plans.json"
    cache.write_text(json.dumps({"version": 1, "plans": plans}))
    monkeypatch.setenv("REPRO_SD_PLAN_CACHE", str(cache))
    sched = ContinuousScheduler(server)
    assert sched.estimator.estimate_ms("g", 4) == pytest.approx(4.0)


def test_server_warmup_compiles_full_ladder():
    server = _server(max_batch=8)
    n = server.warmup(["g"])
    assert n == len(server.buckets())
    assert {k[1] for k in server._compiled} == set(server.buckets())
    # warm again: nothing new
    assert server.warmup(["g"]) == 0


# ---------------------------------------------------------------------------
# CLI: --dryrun exercises the async path with deadlines enabled
# ---------------------------------------------------------------------------

def test_dryrun_uses_async_scheduler_with_deadlines():
    from repro.launch.serve_gen import main
    results, stats = main(["--dryrun"])
    # scheduler stats: latency percentiles + shed accounting
    assert stats["shed"] == 0
    assert stats["latency_ms"]["p95"] is not None
    assert stats["served_on_time"] == stats["served"] == 8
    assert stats["requests"] == 8

