"""Batched generative-network serving on the SD inference engine.

This is THE generative serving entrypoint (the LM counterpart is
:mod:`repro.launch.serve`).  The ROADMAP north-star is heavy traffic:
single-sample generator calls waste the accelerator, so the server

* groups queued requests by network (``launch/batching.take_group`` —
  the same helper the LM server uses for prompt-length grouping),
* pads each group's batch up to a power-of-two *bucket* so the compile
  cache sees a small closed set of shapes: one jitted executable per
  ``(arch, bucket, dtype)`` cell, however many request counts arrive,
* runs the whole bucket through a :class:`repro.engine.SDEngine`-backed
  model — filters presplit + BN-folded exactly once at bind, nothing
  offline on the hot path — with the engine's execution backend chosen
  per jax backend (fused Pallas kernel on TPU, grouped-XLA elsewhere),
* optionally runs on a (data, model) device mesh
  (``launch/mesh.make_dev_mesh``) under one ``shard_map`` per cell:
  ``--dp N`` shards the batch axis over 'data', ``--mp N`` Cout-shards
  each shardable deconv layer's split filters over 'model' (the
  engine binds plans with ``NamedSharding`` placement; one all-gather
  per sharded layer re-assembles the channel axis in the epilogue) —
  DP adds request throughput, MP makes a *single* launch faster,
* keys kernel tile plans to the *bucket* batch it launches
  (``engine.plans_for_batch``), and with ``--pretune`` measures and
  persists the winning ``(th, tw, tcin, tcout)`` tile for every
  (net, bucket, layer) geometry at server start — bind-time
  ``plan_batch=1`` tiles no longer leak into batch-16 launches.

Requests are served by the async continuous-batching scheduler
(:mod:`repro.serving`): it re-forms a bucket at every launch boundary,
honours ``--deadline-ms`` with admission control and supports live
checkpoint hot-swap.  :func:`serve_async` runs a whole batch of requests
through it.

  PYTHONPATH=src python -m repro.launch.serve_gen --nets dcgan,sngan \
      --requests 32 --max-batch 16 --deadline-ms 500
  PYTHONPATH=src python -m repro.launch.serve_gen --dryrun   # CI smoke
"""

from __future__ import annotations

import argparse
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.accounting import WORKLOADS, LayerSpec, NetworkSpec
from repro.launch.batching import pow2_bucket, pow2_floor
from repro.launch.mesh import make_dev_mesh
from repro.models.generative import GenerativeModel
from repro.serving.handoff import stack_rows

ALL_NETS = ("dcgan", "sngan", "artgan", "gpgan", "mde", "fst",
            "wavegan", "voxgan", "segnet", "pix2pix")


@dataclass
class GenRequest:
    """One inference request: a single un-batched generator input."""
    rid: int
    net: str
    latent: Any                 # shape == model.input_shape(1)[1:]


def reduced_spec() -> NetworkSpec:
    """Tiny two-deconv generator for --dryrun / CI smoke."""
    return NetworkSpec("DCGAN-dryrun", [
        LayerSpec("fc", 16, 4 * 4 * 32, name="project"),
        LayerSpec("deconv", 32, 16, k=5, s=2, in_hw=(4, 4), name="d1"),
        LayerSpec("deconv", 16, 3, k=5, s=2, in_hw=(8, 8), name="d2"),
    ])


def reduced_specs() -> Dict[str, NetworkSpec]:
    """One tiny spec per workload family (2-D image, 1-D audio, 3-D
    voxel, 2-D segmentation decoder) so --dryrun smokes the whole rank
    space end to end."""
    return {
        "dcgan-dryrun": reduced_spec(),
        "wavegan-dryrun": NetworkSpec("WaveGAN-dryrun", [
            LayerSpec("fc", 8, 8 * 8, name="project"),
            LayerSpec("deconv", 8, 4, k=9, s=2, in_hw=(8,), name="up1"),
            LayerSpec("deconv", 4, 1, k=9, s=2, in_hw=(16,),
                      name="to_audio"),
        ]),
        "voxgan-dryrun": NetworkSpec("VoxGAN-dryrun", [
            LayerSpec("fc", 8, 2 ** 3 * 8, name="project"),
            LayerSpec("deconv", 8, 4, k=4, s=2, in_hw=(2, 2, 2),
                      name="up1"),
            LayerSpec("deconv", 4, 1, k=4, s=2, in_hw=(4, 4, 4),
                      name="to_vox"),
        ]),
        "segnet-dryrun": NetworkSpec("SegNet-dryrun", [
            LayerSpec("conv", 3, 8, k=3, s=2, in_hw=(8, 8), name="e1"),
            LayerSpec("deconv", 8, 4, k=4, s=2, in_hw=(4, 4), name="d1"),
            LayerSpec("conv", 4, 3, k=3, s=1, in_hw=(8, 8),
                      name="logits"),
        ], final_tanh=False),
    }


class GenServer:
    """Slot-based batched generative inference service on SDEngine."""

    def __init__(self, nets=("dcgan",), dtype=jnp.float32,
                 backend: str = "auto", max_batch: int = 16, dp: int = 1,
                 mp: int = 1, seed: int = 0,
                 specs: Optional[Dict[str, NetworkSpec]] = None,
                 calib: int = 0):
        # dtype="int8" selects the quantized serving path: engines bind
        # int8 plans (per-channel weight quant at bind, per-sample
        # activation quant + dequant epilogue on the hot path), while
        # latents/params/outputs stay f32 — int8 is an execution dtype,
        # not an IO dtype.  The compile-cache key says "int8", so float
        # and int8 cells of the same (net, bucket) coexist.
        #
        # calib=N (int8 only) additionally runs an N-latent calibration
        # sweep per net at bind: static per-layer activation scales
        # replace the per-sample amax pass, and consecutive deconv
        # layers chain int8 activations through HBM (the scales are
        # persisted to the calibration cache under "<net>/max").
        self.calib = int(calib)
        self.engine_dtype = "native"
        if isinstance(dtype, str) and dtype == "int8":
            self.engine_dtype = "int8"
            dtype = jnp.float32
        self.dtype = jnp.dtype(dtype)
        self.dtype_name = ("int8" if self.engine_dtype == "int8"
                           else self.dtype.name)
        self.backend = backend
        # The cap is ALSO the group-size bound, so it must itself be a
        # power of two or pow2_bucket's clamped cap would fall below a
        # full group and run_group would feed a mis-sized batch to the
        # compiled cell — clamp once here (regression: non-pow2 caps
        # used to leak non-pow2 bucket shapes into the compile cache).
        self.max_batch = pow2_floor(max(1, int(max_batch)))
        self.dp = int(dp)
        self.mp = int(mp)
        self.seed = seed
        self._specs = dict(specs or {})
        for n in nets:
            if n not in self._specs:
                self._specs[n] = WORKLOADS[n]()
        self._models: Dict[str, Tuple[GenerativeModel, Any]] = {}
        self._serving: Dict[str, Tuple[Any, Any, Any]] = {}
        self._compiled: Dict[Tuple, Any] = {}
        self.compile_count = 0          # incremented at trace time
        self.group_ms: Dict[str, float] = {}   # last run_group's phases
        self.group_join_bytes = 0       # and the bytes its joins wrote
        self._mesh = None
        if self.dp > 1 or self.mp > 1:
            need = self.dp * self.mp
            if len(jax.devices()) < need:
                raise ValueError(
                    f"--dp {self.dp} --mp {self.mp} needs {need} "
                    f"devices, have {len(jax.devices())} (set "
                    "XLA_FLAGS=--xla_force_host_platform_device_count=N "
                    "to simulate on CPU)")
            # (data, model) mesh: batches shard over 'data', each
            # shardable deconv layer's Cout over 'model' (the engine
            # binds plans with NamedSharding placement; narrow layers
            # replicate, see SDEngine._layer_shards).
            self._mesh = make_dev_mesh(self.dp, self.mp)

    # ---- model / compile caches -----------------------------------------
    def model(self, net: str) -> Tuple[GenerativeModel, Any]:
        """Bound (model, params) per net: the engine presplits here,
        exactly once per server lifetime."""
        if net not in self._models:
            # head semantics ride on the spec (NetworkSpec.final_tanh)
            m = GenerativeModel(self._specs[net], deconv_impl="sd_kernel",
                                engine_backend=self.backend,
                                engine_dtype=self.engine_dtype,
                                engine_mesh=self._mesh)
            params = m.init(jax.random.PRNGKey(self.seed),
                            dtype=self.dtype)
            if self.engine_dtype == "int8" and self.calib > 0:
                # Static activation calibration: one deterministic sweep
                # per server lifetime, before any cell compiles — every
                # (net, bucket) executable traces against chained plans.
                m.calibrate(params, n=self.calib, seed=self.seed,
                            save_key=f"{net}/max")
            self._models[net] = (m, params)
        return self._models[net]

    def _serving_args(self, net: str, bucket: int):
        """(params the plans do not hold, bound plans) for the compiled
        call.  The deconv weights, scales and biases live pre-split
        inside the plans — shipping the raw filters too would feed the
        executable dead operands (and replicate them across the dp
        mesh); a deconv layer's norm ``gamma``/``beta`` still go.
        Plans carry tiles resolved for *this bucket's batch*
        (``engine.plans_for_batch``), so a ``plan_batch=1`` bind no
        longer leaks its tiny-batch tiles into batch-16 launches.
        Cached per (net, bucket), keyed on the live
        params object, so the serving loop does no per-group dict
        rebuilding; a rebind (new params) invalidates."""
        model, params = self.model(net)
        key = (net, bucket)
        cached = self._serving.get(key)
        if cached is None or cached[0] is not params:
            deconv = {l.name for l in model.spec.deconv_layers()}
            lean = {name: ({k: v for k, v in p.items()
                            if k not in ("w", "scale", "b")}
                           if name in deconv else p)
                    for name, p in params.items()}
            self._serving[key] = (params, lean,
                                  model.engine.plans_for_batch(bucket))
        _, lean, plans = self._serving[key]
        return lean, plans

    def buckets(self) -> List[int]:
        """The closed set of batch buckets this server can launch: the
        dp-rounded pow2 ladder up to ``max_batch``."""
        out, n = [], 1
        while n <= self.max_batch:
            b = self.bucket(n)
            if b not in out:
                out.append(b)
            n *= 2
        return out

    def pretune(self, iters: int = 3) -> Dict[str, Any]:
        """Warm the autotune plan cache for every (net, bucket) geometry
        this server will actually execute (``serve_gen --pretune``):
        each deconv layer of each net is measured at every bucket batch
        and the winning ``(th, tw, tcin, tcout)`` tile is persisted —
        so no launch ever falls back to the heuristic because it was
        bound at a different batch.  No-op on the xla backend (tiles
        only steer the fused kernels)."""
        tuned: Dict[str, Any] = {}
        buckets = self.buckets()
        for net in self._specs:
            model, _ = self.model(net)
            if model.engine is None:
                continue
            tuned.update(model.engine.pretune(buckets, iters=iters))
        return tuned

    def warmup(self, nets: Optional[List[str]] = None) -> int:
        """Compile every ``(net, bucket, dtype)`` cell of the bucket
        ladder up front (one tiny launch per cell), so live traffic
        never pays a trace inside a request's latency — the serving
        analogue of ``--pretune`` for the jit cache.  Returns the
        number of cells compiled.  After warmup the compiled-shape set
        is closed: the async scheduler asserts no launch ever retraces
        an existing cell."""
        before = self.compile_count
        for net in (nets if nets is not None else list(self._specs)):
            model, _ = self.model(net)
            shape = model.input_shape(1)[1:]
            for b in self.buckets():
                z = jnp.zeros((b, *shape), self.dtype)
                lean, plans = self._serving_args(net, b)
                jax.block_until_ready(
                    self.compiled(net, b)(lean, plans, z))
        return self.compile_count - before

    def bucket(self, n: int) -> int:
        b = pow2_bucket(n, self.max_batch)
        if self.dp > 1:
            # shard_map needs batch % dp == 0 (dp need not be a power
            # of two): round the pow2 bucket up to a dp multiple.  The
            # closed set stays {dp-roundups of the pow2 ladder}.
            b = -(-max(b, self.dp) // self.dp) * self.dp
        return b

    def cell_key(self, net: str, bucket: int) -> Tuple:
        """Compile-cache key of one executable cell.  Mesh-less servers
        keep the historical ``(net, bucket, dtype)`` key; on a mesh the
        shape ``dpNxmpM`` is part of the key — the same (net, bucket)
        compiled for a different mesh is a different executable, and
        the scheduler's zero-recompile swap assertion checks *this* key
        (via ``getattr``), so it stays honest under --dp/--mp."""
        if self._mesh is None:
            return (net, bucket, self.dtype_name)
        return (net, bucket, self.dtype_name,
                f"dp{self.dp}xmp{self.mp}")

    def estimate_ms(self, net: str, bucket: int) -> Optional[float]:
        """Cold-start service-time estimate for one (net, bucket) cell,
        from the engine's measured per-layer plan entries.  The engine
        keys lookups on what one device launches (per-device batch,
        per-shard Cout, mesh degree), so the seed the scheduler's
        admission control starts from is not wrong by the parallelism
        factor."""
        model, _ = self.model(net)
        if model.engine is None:
            return None
        return model.engine.estimate_ms(bucket)

    def join_bytes(self, net: str, bucket: int) -> int:
        """Bytes one launch of the cell writes by concatenating skip
        outputs ahead of decoder inputs (``NetworkSpec.join_elems``):
        the traffic a kernel reading two operands would not make."""
        return bucket * self._specs[net].join_elems() * self.dtype.itemsize

    def compiled(self, net: str, bucket: int):
        """The jitted padded-batch executable for one cell (see
        :meth:`cell_key`).

        Since the ``repro.sd`` redesign the engine's bound plans are
        pytrees, so params AND plans are passed *through* jit as
        arguments (``GenerativeModel.apply_with_plans``) rather than
        closed over: rebinding weights (new checkpoint, dtype sweep)
        reuses the compiled executable — only shapes key the cache.

        On a mesh the cell is one ``shard_map`` over the whole forward:
        x/y batch-sharded over 'data', each bound plan's leaves carried
        at its own ``shard_specs`` (ws/bias/wscale Cout-sharded over
        'model' for sharded layers, replicated otherwise — the spec
        tree mirrors the NamedSharding placement ``plan.bind(mesh=)``
        already gave the arrays, so shard_map moves no filter bytes),
        non-deconv params replicated.
        """
        key = self.cell_key(net, bucket)
        if key not in self._compiled:
            model, _ = self.model(net)

            def f(params, plans, x):
                self.compile_count += 1      # runs only while tracing
                return model.apply_with_plans(params, plans, x)

            if self._mesh is not None:
                ndim = len(model.input_shape(bucket))
                spec = P(*(("data",) + (None,) * (ndim - 1)))
                _, plans = self._serving_args(net, bucket)
                plan_specs = {name: p.shard_specs()
                              for name, p in plans.items()}
                from jax.experimental.shard_map import shard_map
                f = shard_map(f, mesh=self._mesh,
                              in_specs=(P(), plan_specs, spec),
                              out_specs=spec, check_rep=False)
            self._compiled[key] = jax.jit(f)
        return self._compiled[key]

    # ---- live checkpoint hot-swap ---------------------------------------
    def swap_checkpoint(self, net: str, params) -> None:
        """Rebind ``net`` to a new parameter set (live checkpoint
        hot-swap).  The engine re-splits + BN-folds the new filters
        (the once-per-checkpoint offline phase); every compiled
        ``(net, bucket, dtype)`` executable is reused as-is, because
        params and bound plans are jit *arguments*, not closures
        (PR 3's rebind-without-recompile, wired end to end here).  The
        per-bucket ``_serving`` snapshots invalidate themselves — they
        are keyed on the live params object's identity.  Callers that
        serve concurrently with swapping (the async scheduler) apply
        this only at launch boundaries, so a single launch never mixes
        weight sets."""
        model, _ = self.model(net)
        if model.engine is not None:
            model.engine.bind(params)
        self._models[net] = (model, params)

    # ---- serving ---------------------------------------------------------
    def run_group(self, net: str, latents: List[Any]):
        """Pad a same-net group to its bucket, run, crop the padding.

        Its two phases are profiler spans, and their host ms are left in
        ``group_ms`` for the scheduler's launch record: ``serve.inputs``
        (``inputs_ms``: the padded batch from
        :func:`repro.serving.handoff.stack_rows`, stacked on the host and
        copied to the device in one call) and ``serve.dispatch``
        (``dispatch_ms``: the compiled call and the crop, enqueued, not
        waited on); ``group_join_bytes`` is the launch's
        :meth:`join_bytes`."""
        n = len(latents)
        bucket = self.bucket(n)
        lean_params, plans = self._serving_args(net, bucket)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("serve.inputs"):
            x = stack_rows(latents, bucket, self.dtype)
        t1 = time.perf_counter()
        with jax.profiler.TraceAnnotation("serve.dispatch"):
            y = self.compiled(net, bucket)(lean_params, plans, x)[:n]
        self.group_ms = {"inputs_ms": (t1 - t0) * 1e3,
                         "dispatch_ms": (time.perf_counter() - t1) * 1e3}
        self.group_join_bytes = self.join_bytes(net, bucket)
        return y

    def random_requests(self, net: str, n: int, seed: int = 1
                        ) -> List[GenRequest]:
        model, _ = self.model(net)
        shape = model.input_shape(n)
        # Host rows: run_group stacks a launch's rows on the host.
        z = np.asarray(jax.random.normal(jax.random.PRNGKey(seed), shape,
                                         self.dtype))
        return [GenRequest(rid=i, net=net, latent=z[i]) for i in range(n)]


def serve_async(server: GenServer, requests: List[GenRequest],
                deadline_ms: Optional[float] = None):
    """Run ``requests`` through the continuous-batching scheduler
    (:mod:`repro.serving`) — everything arrives at t0, deadlines are
    relative to arrival.  Returns ({rid: output}, stats): the
    scheduler's stats plus ``wall_s``, ``requests`` (served) and
    ``req_per_s``."""
    from repro.serving import ContinuousScheduler
    sched = ContinuousScheduler(server)
    t0 = sched.clock.now()
    for r in requests:
        sched.submit(r.net, r.latent, rid=r.rid, arrival_t=t0,
                     deadline_ms=deadline_ms)
    results = sched.run()
    wall = sched.clock.now() - t0
    stats = sched.stats(wall_s=wall)
    stats["wall_s"] = wall
    stats["requests"] = stats["served"]
    stats["req_per_s"] = (stats["served"] / wall if wall
                          else float("inf"))
    return results, stats


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--nets", default="dcgan",
                    help=f"comma list from {ALL_NETS}")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=16)
    ap.add_argument("--dp", type=int, default=1,
                    help="shard_map data-parallel degree over the batch")
    ap.add_argument("--mp", type=int, default=1,
                    help="model-parallel degree: Cout-shard each "
                         "shardable deconv layer's split filters over "
                         "the mesh's 'model' axis (needs dp*mp devices)")
    ap.add_argument("--backend", default="auto",
                    choices=["auto", "fused", "xla", "winograd"])
    ap.add_argument("--dtype", default="float32",
                    choices=["float32", "bfloat16", "int8"],
                    help="int8 = quantized engine plans (f32 IO)")
    ap.add_argument("--calib", type=int, default=0, metavar="N",
                    help="int8 only: calibrate static activation "
                         "scales on N latents per net and chain int8 "
                         "activations between consecutive deconv "
                         "layers (0 = dynamic per-sample scales)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request deadline (relative to arrival); "
                         "the scheduler sheds requests it cannot meet")
    ap.add_argument("--dryrun", action="store_true",
                    help="2 requests on a reduced arch (CI smoke)")
    ap.add_argument("--pretune", action="store_true",
                    help="warm the autotune plan cache for every "
                         "(net, bucket) geometry before serving")
    args = ap.parse_args(argv)

    if args.dryrun:
        specs = reduced_specs()
        if args.backend == "winograd":
            # The pinned fast-algorithm backend covers ranks 1-2 with
            # taps <= 5; drop the reduced specs outside that envelope
            # (the 3-D voxel smoke) instead of failing the whole smoke.
            from repro.kernels.winograd import supported
            specs = {n: sp for n, sp in specs.items()
                     if all(supported((-(-l.k // l.s),) * l.rank)
                            for l in sp.deconv_layers())}
        nets = sorted(specs)
        n_requests = 2
        if args.deadline_ms is None:
            # CI smokes the deadline machinery end to end (requests
            # carry real deadlines through admission control), with a
            # bound generous enough that a loaded CI box never sheds.
            args.deadline_ms = 120_000.0
    else:
        nets = args.nets.split(",")
        specs = None
        n_requests = args.requests

    dtype = "int8" if args.dtype == "int8" else jnp.dtype(args.dtype)
    if args.calib and args.dtype != "int8":
        ap.error("--calib requires --dtype int8")
    server = GenServer(nets=nets, dtype=dtype,
                       backend=args.backend, max_batch=args.max_batch,
                       dp=args.dp, mp=args.mp, specs=specs,
                       calib=args.calib)
    if args.pretune:
        t0 = time.time()
        tuned = server.pretune()
        print(f"pretuned {len(tuned)} (layer, bucket) geometries over "
              f"buckets {server.buckets()} in {time.time()-t0:.1f}s")
    requests: List[GenRequest] = []
    for i, net in enumerate(nets):
        reqs = server.random_requests(net, n_requests, seed=i + 1)
        for r in reqs:
            r.rid = len(requests)
            requests.append(r)

    results, stats = serve_async(server, requests,
                                 deadline_ms=args.deadline_ms)
    print(f"served {stats['requests']} requests in "
          f"{stats['wall_s']:.2f}s ({stats['req_per_s']:.1f} req/s, "
          f"{stats['launches']} launches, {stats['compiles']} "
          f"compiles, {stats['shed']} shed)")
    lat = stats["latency_ms"]
    print(f"  latency p50 {lat['p50']}ms p95 {lat['p95']}ms "
          f"p99 {lat['p99']}ms; goodput "
          f"{stats['goodput_rps']} req/s; mean occupancy "
          f"{stats['mean_occupancy']}")
    for key in stats["compile_cache"]:
        print(f"  compiled cell: {key}")
    for rid in sorted(results)[:2]:
        out = np.asarray(results[rid])
        print(f"  req{rid}: out{out.shape} mean {out.mean():+.4f}")
    return results, stats


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
