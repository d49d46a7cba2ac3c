"""One run of one cell: set-up, the measured window, the check against the
plain reference, and what the metric readers read.

Everything specific to a configuration, a traffic mix or a metric lives in
data files and small modules found by name (see README.md); this module
holds only the general machinery.  From the program it uses the public
serving entry points (``GenServer``, ``ContinuousScheduler``) and the
layer specs in ``WORKLOADS``, which it checks against the configuration
files before anything runs.
"""

from __future__ import annotations

import contextlib
import gc
import heapq
import importlib.util
import json
import os
import sys
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import numpy as np

import flops
import traffic as traffic_mix
from traffic import input_pool

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".bench_cache", "jax")
HOST_SPANS = ("bench.traffic", "sched.step", "bench.sample")
# Set-up ends with this long a window of the cell's own traffic, unmeasured:
# on a TPU v5e the first window after the warm-up launches ran slow
# (p95 616 ms in its first quarter against 18 ms in the next window).
PREROLL_S = 1.0
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def read_json(*parts) -> Any:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(path: str):
    """Import a file by path (metric names hold dots, so not by name)."""
    name = "bench_" + os.path.relpath(path, BENCH).replace(os.sep, "_")
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def enable_compile_cache() -> None:
    """JAX's persistent compile cache at a fixed path in the checkout,
    for every program however small or quick to compile."""
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def find_chips(chips: int):
    import jax
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        raise NoChip(f"no TPU: JAX's first device is {d.platform} "
                     f"({d.device_kind})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    return devices


def peaks_for(device_kind: str) -> dict:
    table = read_json(BENCH, "peaks.json")
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"bench/peaks.json ({sorted(table)})")
    return table[device_kind]


class CompileCounter:
    """Counts every trace and backend compile JAX makes in this process."""

    _instance = None

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event, duration, **kwargs):
        if event in COMPILE_EVENTS:
            self.count += 1

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance


# ---------------------------------------------------------------------------
# The cell: BENCHMARK.json entry + configurations + workload + traffic files
# ---------------------------------------------------------------------------

@dataclass
class Cell:
    name: str
    chips: int
    configs: Dict[str, dict]        # every configuration served, by name
    shares: Dict[str, float]        # each one's share of the requests
    workload: dict
    traffic: Any                    # the mix's ``Arrivals`` (traffic.py)
    end_to_end: List[dict]
    per_layer: List[dict]

    @classmethod
    def load(cls, name: str, root: str = ROOT) -> "Cell":
        """The cell ``name``.  Its workload file may list the configurations
        it serves with their shares of the requests (``"nets"``); by
        default it serves its BENCHMARK.json configuration alone."""
        bench = read_json(root, "BENCHMARK.json")
        entry = {w["name"]: w for w in bench["workloads"]}.get(name)
        if entry is None:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        e2e = [m for m in bench["end_to_end"]
               if name in m.get("workloads", [name])]
        moved = {m["name"] for m in e2e}
        per_layer = [m for m in bench["per_layer"]
                     if (name in m["workloads"] if "workloads" in m
                         else m["moves"] in moved)]
        workload = read_json(BENCH, "workloads", name + ".json")
        shares = workload.get("nets", {entry["config"]: 1.0})
        return cls(name=name, chips=int(entry["chips"]),
                   configs={c: read_json(BENCH, "configs", c + ".json")
                            for c in shares},
                   shares=shares, workload=workload,
                   traffic=traffic_mix.load(entry["traffic"]),
                   end_to_end=e2e, per_layer=per_layer)

    @property
    def max_batch(self) -> int:
        return int(self.workload["max_batch"])


def input_shape(config: dict):
    first = config["layers"][0]
    if first["kind"] == "fc":
        return (first["cin"],)
    return (*first["in_hw"], first["cin"])


def check_spec(spec, config: dict) -> None:
    """The program's layer spec must be the configuration file's."""
    want = config["layers"]
    keys = ("kind", "name", "cin", "cout", "k", "s", "in_hw", "padding")
    got = []
    for layer in spec.layers:
        d = {"kind": layer.kind, "name": layer.name, "cin": layer.cin,
             "cout": layer.cout}
        if layer.kind != "fc":
            d.update(k=layer.k, s=layer.s, in_hw=list(layer.in_hw),
                     padding=layer.padding)
        got.append(d)
    norm = [{k: w[k] for k in keys if k in w} for w in want]
    if got != norm or bool(spec.final_tanh) != bool(config["final_tanh"]):
        raise ValueError(f"the program's {config['net']!r} spec differs "
                         f"from bench/configs/{config['name']}.json:\n"
                         f"program {got}\nconfig  {norm}")


def _splitmix(x: np.ndarray) -> np.ndarray:
    x = x + np.uint64(0x9E3779B97F4A7C15)
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


def _seed_key(seed: int, tag: int) -> np.uint64:
    return np.random.SeedSequence([seed, tag]).generate_state(1, np.uint64)[0]


class Sampler:
    """Keeps the outputs of a seeded sample of ``k`` served requests: the
    ``k`` whose seeded hash of the request id is smallest (a bottom-k
    sample), so the sample is uniform over the whole window without
    knowing its length.  Every other output is dropped at once."""

    def __init__(self, seed: int, k: int):
        self.key = _seed_key(seed, 0x5A3)
        self.k = int(k)
        self.heap: List[tuple] = []          # (-priority, rid, output)

    def take(self, results: Dict[int, Any]) -> None:
        if not results:
            return
        rids = np.fromiter(results.keys(), np.int64, len(results))
        prio = _splitmix(rids.astype(np.uint64) ^ self.key)
        if len(self.heap) >= self.k:
            idx = np.nonzero(prio < np.uint64(-self.heap[0][0]))[0]
        else:
            idx = range(len(rids))
        for i in idx:
            rid = int(rids[i])
            item = (-int(prio[i]), rid, results[rid])
            if len(self.heap) < self.k:
                heapq.heappush(self.heap, item)
            else:
                heapq.heappushpop(self.heap, item)
        results.clear()

    def outputs(self) -> Dict[int, Any]:
        return {rid: out for _, rid, out in self.heap}


# ---------------------------------------------------------------------------
# What one window produced
# ---------------------------------------------------------------------------

@dataclass
class Run:
    """The record of one window, which the metric readers read."""
    configs: Dict[str, dict]        # configuration of each net served
    setup_s: float
    window_s: float                 # host clock, window start to last done
    launches: List[dict]            # ServingMetrics.launches, drain too
    window_launches: int            # how many of them the window made
    served: List[dict]              # ServingMetrics.served, due in window
    attempted: int
    failed: int
    peak: dict
    trace: Optional[dict] = None
    outputs: Dict[int, np.ndarray] = field(default_factory=dict)
    memory_peak_bytes: int = 0

    @property
    def dtype(self) -> str:
        return next(iter(self.configs.values()))["dtype"]

    def window(self) -> List[dict]:
        """The launches made in the window."""
        return self.launches[:self.window_launches]

    @property
    def images(self) -> int:
        """Images of the launches made in the window."""
        return sum(r["n"] for r in self.window())

    def latencies_ms(self) -> List[float]:
        return [r["latency_ms"] for r in self.served]

    def mean_launch_ms(self) -> Optional[float]:
        if not self.launches:
            return None
        return sum(r["ms"] for r in self.launches) / len(self.launches)

    def itemsize(self) -> int:
        return flops.ITEMSIZE[self.dtype]

    def peak_flops(self) -> float:
        return self.peak[flops.PEAK_KEY[self.dtype]]

    def model_flops(self) -> int:
        """Useful FLOPs of the images of the window's launches."""
        return sum(r["n"] * flops.model_flops(
            self.configs[r["net"]]["layers"]) for r in self.window())

    def sd_kernel_calls(self) -> List[dict]:
        """FLOPs and bytes of every split-deconv kernel call the window's
        launches made."""
        calls = []
        for launch in self.window():
            calls += flops.sd_kernel_launches(
                self.configs[launch["net"]]["layers"], launch["bucket"],
                self.itemsize())
        return calls


def percentile(values: List[float], q: float) -> Optional[float]:
    if not values:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


# ---------------------------------------------------------------------------
# Server, weights and inputs of one cell
# ---------------------------------------------------------------------------

class Model:
    """One configuration the cell serves: its plain reference, and its
    weights and inputs from the seed."""

    def __init__(self, config: dict, index: int, workload: dict):
        import jax
        import jax.numpy as jnp
        self.config, self.index = config, index
        self.net = config["net"]
        self.ref = load_module(os.path.join(
            BENCH, "references", config["reference"] + ".py"))
        self.dtype = jnp.dtype(config["dtype"])
        self.pool_size = int(workload["pool"])
        self.block = int(workload["reference_block"])
        self._init = jax.jit(self._make_params)
        self._ref_fns: Dict[str, Any] = {}

    def _make_params(self, key):
        import jax
        params = self.ref.init(self.config["layers"], key)
        return jax.tree_util.tree_map(lambda a: a.astype(self.dtype), params)

    def reseed(self, seed: int) -> None:
        """Weights (one jitted call on the device) and the input pool."""
        import jax
        state = np.random.SeedSequence([seed, 0x3E1, self.index])
        key = jax.random.wrap_key_data(
            state.generate_state(2).astype(np.uint32))
        self.params = self._init(key)
        self.pool = input_pool(input_shape(self.config),
                               self.config["input"]["dist"],
                               self.pool_size, seed + self.index)

    def input(self, rid: int) -> np.ndarray:
        return self.pool[rid % len(self.pool)]

    def reference(self, rids, precision: str = "highest") -> np.ndarray:
        """The plain reference's outputs for the requests ``rids``, in
        blocks of ``reference_block`` rows."""
        import jax
        fn = self._ref_fns.get(precision)
        if fn is None:
            config = self.config
            fn = jax.jit(lambda p, x: self.ref.forward(config, p, x,
                                                       precision))
            self._ref_fns[precision] = fn
        outs = []
        for i in range(0, len(rids), self.block):
            x = np.stack([self.input(r) for r in rids[i:i + self.block]])
            n = len(x)
            if n < self.block:               # one compiled block shape
                x = np.concatenate([x, np.zeros((self.block - n,
                                                 *x.shape[1:]), x.dtype)])
            outs.append(np.asarray(fn(self.params, x))[:n])
        return np.concatenate(outs)


class Bench:
    """The program under test, set up for one cell from a seed."""

    def __init__(self, cell: Cell, seed: int):
        sys.path.insert(0, os.path.join(ROOT, "src"))
        from repro.core.accounting import WORKLOADS
        from repro.launch.serve_gen import GenServer
        self.cell = cell
        self.models: Dict[str, Model] = {}
        for i, config in enumerate(cell.configs.values()):
            check_spec(WORKLOADS[config["net"]](), config)
            self.models[config["net"]] = Model(config, i, cell.workload)
        first = next(iter(self.models.values()))
        for m in self.models.values():
            if (m.dtype, m.config["backend"]) != (first.dtype,
                                                  first.config["backend"]):
                raise ValueError("one server serves one dtype and backend: "
                                 f"{m.config['name']} differs")
        shares = np.array(list(cell.shares.values()), np.float64)
        self._cum = np.cumsum(shares) / shares.sum()
        self.nets = list(self.models)
        self.counter = CompileCounter.get()
        self.server = GenServer(nets=self.nets, dtype=first.dtype,
                                backend=first.config["backend"],
                                max_batch=cell.max_batch,
                                seed=seed % 2 ** 31)
        if self.server.max_batch != cell.max_batch:
            raise ValueError(f"max_batch {cell.max_batch} is not a power "
                             "of two")
        self.reseed(seed)

    def reseed(self, seed: int) -> None:
        """Weights and inputs of every configuration from ``seed``; the
        server is rebound to the new weights."""
        self.seed = seed
        self._net_key = _seed_key(seed, 0x4E7)
        for m in self.models.values():
            m.reseed(seed)
            self.server.swap_checkpoint(m.net, m.params)

    def net_of(self, rid: int) -> str:
        """The net request ``rid`` goes to, drawn from the seed by the
        configurations' shares."""
        if len(self.nets) == 1:
            return self.nets[0]
        u = int(_splitmix(np.array([rid], np.uint64) ^ self._net_key)[0])
        u /= 2.0 ** 64
        return self.nets[int(np.searchsorted(self._cum, u, side="right"))]

    def submit(self, sched, rid: int, due: float, net: str = None) -> None:
        net = net or self.net_of(rid)
        sched.submit(net, self.models[net].input(rid), rid=rid,
                     arrival_t=due)

    # ---- set-up: compile and run once every shape the window uses ---------
    def warm(self) -> None:
        """Runs one launch of every group size the cell's traffic forms,
        for each net, through the scheduler, so every program the window
        calls is compiled, then ``PREROLL_S`` of the cell's traffic."""
        from repro.serving import ContinuousScheduler
        rid = 0
        for net in self.nets:
            for n in self.cell.traffic.group_sizes(self.cell.max_batch):
                sched = ContinuousScheduler(self.server)
                now = sched.clock.now()
                for _ in range(n):
                    self.submit(sched, rid, now, net)
                    rid += 1
                sched.run()
                Sampler(self.seed, 1).take(sched.results)
        self.run_window(PREROLL_S, time.perf_counter())

    # ---- the measured window --------------------------------------------
    def run_window(self, seconds: float, t_process: float,
                   trace_dir: Optional[str] = None, traffic=None) -> Run:
        """Drives ``ContinuousScheduler.step`` for ``seconds`` under the
        cell's traffic (or ``traffic``), keeps the outputs of a seeded
        sample, and returns the record.  Raises if anything compiles
        inside the window."""
        import jax
        from repro.serving import ContinuousScheduler
        traffic = traffic or self.cell.traffic
        sched = ContinuousScheduler(self.server)
        clock, results = sched.clock, sched.results
        served = sched.metrics.served
        sampler = Sampler(self.seed, int(self.cell.workload["sample"]))
        span = (jax.profiler.TraceAnnotation if trace_dir
                else lambda name: contextlib.nullcontext())
        compiles0, traces0 = self.server.compile_count, self.counter.count
        if trace_dir:
            jax.profiler.start_trace(trace_dir)
        with span("bench.window"):
            t0 = clock.now()
            setup_s = time.perf_counter() - t_process
            traffic.start(t0, seconds, self.seed, self.cell.max_batch)
            rid = 0
            while clock.now() < t0 + seconds:
                with span("bench.traffic"):
                    for due in traffic.release(clock.now(), rid, len(served)):
                        self.submit(sched, rid, due)
                        rid += 1
                with span("sched.step"):
                    sched.step()
                with span("bench.sample"):
                    sampler.take(results)
            t_stop = clock.now()
            n_launches = len(sched.metrics.launches)
        if traffic.closed:
            attempted = len(served)
        else:
            # Every request due in the window may finish in the drain.
            for due in traffic.owed(rid):
                self.submit(sched, rid, due)
                rid += 1
            attempted = rid
            limit = t_stop + float(self.cell.workload["drain_s"])
            while len(served) < attempted and clock.now() < limit:
                sched.step()
                sampler.take(results)
        if trace_dir:
            jax.profiler.stop_trace()
        moved = (self.server.compile_count - compiles0,
                 self.counter.count - traces0)
        if any(moved):
            raise RuntimeError(
                f"{moved[0]} serving cells and {moved[1]} programs compiled "
                "inside the measured window; set-up must warm every shape")
        run = Run(configs={n: m.config for n, m in self.models.items()},
                  setup_s=setup_s, window_s=t_stop - t0,
                  launches=list(sched.metrics.launches),
                  window_launches=n_launches,
                  served=list(served), attempted=attempted,
                  failed=attempted - len(served), peak={})
        run.outputs = {rid: np.asarray(out)
                       for rid, out in sampler.outputs().items()}
        return run

    def model_modules(self) -> List[str]:
        """Trace names of the compiled serving cells (``jit_<fn>``)."""
        names = set()
        for net in self.nets:
            fn = self.server.compiled(net, self.cell.max_batch)
            names.add("jit_" + getattr(fn, "__name__", "f"))
        return sorted(names)

    def memory_peak_bytes(self) -> int:
        import jax
        stats = [d.memory_stats() or {}
                 for d in jax.local_devices()[:self.cell.chips]]
        return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)

    def free_server(self) -> None:
        """Drop the program's state (bound plans, compiled cells) so that
        the reference runs in the memory the program held."""
        self.server = None
        gc.collect()

    # ---- the check -------------------------------------------------------
    def reference(self, rids, precision: str = "highest"
                  ) -> Dict[int, np.ndarray]:
        """The plain reference's outputs for the requests ``rids``."""
        out = {}
        for net, m in self.models.items():
            mine = [r for r in sorted(rids) if self.net_of(r) == net]
            if mine:
                out.update(zip(mine, m.reference(mine, precision)))
        return out

    def gaps(self, outputs: Dict[int, np.ndarray],
             ref: Optional[Dict[int, np.ndarray]] = None
             ) -> Dict[str, float]:
        """How far the sampled outputs lie from the reference's, for each
        configuration by its worst request: ``l2_rel_err.<config>``, the
        norm of the gap over the norm of the reference.  A missing,
        misshapen or non-finite output reads infinite."""
        if ref is None:
            ref = self.reference(list(outputs))
        worst = {m.config["name"]: 0.0 for m in self.models.values()}
        for rid, out in outputs.items():
            name = self.models[self.net_of(rid)].config["name"]
            r = np.asarray(ref[rid], np.float64)
            out = np.asarray(out, np.float32)
            if out.shape != r.shape or not np.isfinite(out).all():
                gap = float("inf")
            else:
                gap = float(np.linalg.norm(out - r) / np.linalg.norm(r))
            worst[name] = max(worst[name], gap)
        return {f"l2_rel_err.{name}": v for name, v in worst.items()}


def evaluate(run: Run, metrics: List[dict]):
    """Each metric's reader over the run; a reader that finds nothing to
    read returns None and the metric is left out.  A reader may also have
    ``note(run)``, a word on how to read its number (a roofline's bound).
    Returns (metrics, notes)."""
    out, notes = {}, {}
    for m in metrics:
        reader = load_module(os.path.join(BENCH, "metrics", m["name"] + ".py"))
        value = reader.read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
            if hasattr(reader, "note"):
                notes[m["name"]] = reader.note(run)
    return out, notes
