"""Tile-plan autotuner for the SD Pallas kernels.

The kernels in :mod:`repro.kernels.sd_conv` are parameterised by a tile
plan ``(th, tw, tcin, tcout)`` — output-row band height, output-column
band width (0 = one band spans the full width), input-channel tile and
output-channel tile.  The right plan depends on the layer geometry
(spatial size vs channel depth decides whether rows or channels should
carry the MXU occupancy), so a fixed plan leaves performance on the
table exactly as the paper's related work (HUGE^2, the FPGA design-
methodology line) observes for deconv dataflows.

This module provides:

* :class:`ConvGeom` — the key: the *executed* stride-1 conv geometry
  ``(b, h, w, cin, cout, kt, s)`` where ``h/w`` are the P_I-padded
  input sizes (the kernels' wrappers apply that pad, but the
  geometry — and therefore the cache key — is unchanged), ``cout``
  counts deconv output channels (oc units) and ``s`` is the in-kernel
  interleave factor (1 for the plain conv kernel).  ``tag`` names
  non-forward launches (the backward's input-grad / filter-grad convs)
  so their plans never collide with forward plans of the same shape.
* :func:`heuristic_plan` — a cheap default used when no measured plan
  exists (replaces the old hard-coded ``_pick_th``).
* :func:`vmem_plan_bytes` — the VMEM footprint model the heuristic and
  the candidate filter share: input band (halo included), filter block,
  f32 accumulator and output tile — not just the filter block — with
  the lanes a phase-packed launch saves.
* :func:`candidate_plans` — the search space for a geometry.
* :func:`tune` — measure every candidate with a caller-supplied runner
  and persist the winner to a JSON cache.
* :func:`get_plan` — cache lookup with heuristic fallback; this is what
  ``kernels/ops.py`` consults on every call (trace-safe: pure Python on
  static shapes, no timing).

Cache format (JSON, see DESIGN.md)::

    {"version": 1,
     "plans": {"b1_h12w12_ci256_co128_kt3_s2":
                   {"th": 8, "tw": 0, "tcin": 128, "tcout": 64,
                    "ms": 0.41, "source": "measured", "backend": "tpu"}}}

Entries written before the ``tw`` dimension existed load with ``tw=0``
(full-width bands — exactly what those plans measured).

Entries are gated on the backend they were measured on: interpret-mode
CPU winners never leak into a TPU run (and vice versa).

The cache path defaults to ``~/.cache/repro/sd_plans.json`` and can be
overridden with the ``REPRO_SD_PLAN_CACHE`` environment variable or per
call.
"""

from __future__ import annotations

import json
import os
import time
from dataclasses import asdict, dataclass, replace as dataclasses_replace
from typing import Callable, Dict, List, Optional

import jax

from repro.core.iohelpers import atomic_write_json
from repro.kernels.sd_conv import phase_packed

_ENV_CACHE = "REPRO_SD_PLAN_CACHE"
_DEFAULT_CACHE = os.path.join(os.path.expanduser("~"), ".cache", "repro",
                              "sd_plans.json")

# In-memory mirror of the JSON file so jit tracing never re-reads disk.
_MEM: Dict[str, Dict[str, dict]] = {}


@dataclass(frozen=True)
class KernelPlan:
    """Tile sizes for one kernel launch. ``tcout`` is in oc units (the
    fused kernel's accumulator holds ``tcout * s^2`` phase channels);
    ``tw == 0`` means one band spans the full output width (the only
    shape pre-``tw`` plans ever measured, so old cache entries load
    unchanged)."""
    th: int
    tcin: int
    tcout: int
    tw: int = 0


@dataclass(frozen=True)
class ConvGeom:
    """Geometry of the executed stride-1 split conv (see module doc).

    ``ktw``/``sw`` (0 = "same as ``kt``/``s``", the square 2-D default)
    describe rectangular kernels and per-dim interleave factors — the
    1-D rank lowering runs a ``(1, KT)`` filter with interleave
    ``(1, s)`` through the same Pallas kernel.  ``tag`` distinguishes
    launch *roles* on identical shapes: "" is the forward, "dx" the
    backward's input-grad FULL conv, "dw" the filter-grad conv.  Square
    untagged geometries keep their historical cache keys.
    """
    b: int
    h: int          # padded input rows (Hp)
    w: int          # padded input cols (Wp)
    cin: int
    cout: int       # oc units (deconv C_out; == conv C_out when s == 1)
    kt: int
    s: int          # interleave factor (1: plain conv kernel)
    ktw: int = 0    # col-kernel taps (0: square, == kt)
    sw: int = 0     # col interleave (0: square, == s)
    tag: str = ""   # launch role ("" fwd | "dx" | "dw")
    # Zero-copy launch shape (not part of the cache key: the plan for a
    # padded geometry is reused across crops, an approximation the key
    # always made implicitly).  out_h/out_w are the FINAL deconv output
    # rows/cols; crop_h/crop_w the low-side interleaved-coordinate crop
    # (-1 = unknown, pre-zero-copy callers).  When known, the row/col
    # tile options align output tiles to the final geometry (th*s | OH)
    # — partial trailing blocks waste compute and, off TPU, an extra
    # output slice.
    out_h: int = 0
    out_w: int = 0
    crop_h: int = -1
    crop_w: int = -1
    # Operand dtype of the launch ("" = float32, the historical default
    # — untagged keys are unchanged).  "int8" keys separately AND
    # changes the footprint model: 1-byte input band + filter block
    # (the int32 accumulator and f32 output stay 4-byte), so tile
    # candidates ~4x larger on the operand side become legal.
    dtype: str = ""
    # Compute algorithm of the launch ("" = direct MXU conv, the
    # historical default — existing cache keys are unchanged; "wino" =
    # the Winograd transformed-domain kernel).  Algorithms key
    # separately (their best tiles differ: the Winograd accumulator is
    # alpha^2/m^2 times larger per row) and change the footprint model.
    algo: str = ""
    # Int8-*output* launches (the activation-chained epilogue requants
    # the tile to int8 in VMEM before the interleave write).  False is
    # the historical default — keys unchanged.  True keys separately AND
    # changes the footprint model: the interleaved output tile is 1 byte
    # per element (4x smaller), so wider output tiles become legal; and
    # the launch's HBM write traffic is a quarter of the f32-output
    # launch, which is exactly what the chained path buys.
    qout: bool = False
    # Model-parallel degree of the launch (1 = unsharded, the historical
    # default — keys unchanged).  A Cout-sharded plan launches with
    # ``cout`` already divided by the shard count, but its measured time
    # includes the epilogue all-gather, so an MP-measured entry must
    # never steer a genuinely-small unsharded layer of the same local
    # shape (or vice versa): shards > 1 keys separately.
    shards: int = 1

    def key(self) -> str:
        base = (f"b{self.b}_h{self.h}w{self.w}_ci{self.cin}"
                f"_co{self.cout}_kt{self.kt}_s{self.s}")
        if self.ktw or self.sw:
            base += f"_ktw{self.ktw or self.kt}_sw{self.sw or self.s}"
        if self.dtype:
            base += f"_{self.dtype}"
        if self.algo:
            base += f"_{self.algo}"
        if self.qout:
            base += "_q8out"
        if self.shards > 1:
            base += f"_mp{self.shards}"
        if self.tag:
            base += f"_{self.tag}"
        return base

    @property
    def operand_itemsize(self) -> int:
        """Bytes per element of the input band / filter block."""
        return 1 if self.dtype == "int8" else 4

    @property
    def oh(self) -> int:
        return self.h - self.kt + 1

    @property
    def ow(self) -> int:
        return self.w - (self.ktw or self.kt) + 1

    @classmethod
    def from_deconv(cls, b: int, h: int, w: int, cin: int, cout: int,
                    k: int, s: int, padding=None,
                    output_padding: int = 0,
                    dtype: str = "") -> "ConvGeom":
        """Geometry of the conv that SD runs for a (H,W,Cin,Cout,K,s)
        deconv layer: input padded by P_I = K_T - 1 per side.  When the
        user ``padding`` is known, the final output shape and crop are
        attached (key-neutral) so the tile options can align output
        tiles to the final geometry.  ``dtype`` tags low-precision
        launches (keys and footprint model differ, see the field doc)."""
        kt = -(-k // s)
        pi = kt - 1
        geom = cls(b, h + 2 * pi, w + 2 * pi, cin, cout, kt, s,
                   dtype=dtype)
        if padding is None:
            return geom
        from repro.core.deconv import _pads, deconv_output_shape
        pk = s * kt - k
        pads = _pads(padding)
        oh_f, ow_f = deconv_output_shape((h, w), k, s, padding,
                                         output_padding)
        return dataclasses_replace(
            geom, out_h=oh_f, out_w=ow_f,
            crop_h=pk + pads[0][0], crop_w=pk + pads[1][0])


def lane_tiles(c: int) -> List[int]:
    """Channel tile candidates Mosaic can lay out: the full depth plus
    every multiple of 128 lanes that divides it.  Channels are the lane
    (last) dim of the bands, filter blocks and output tiles, whose block
    dims must be a multiple of 128 or the whole dim."""
    return [c] + [t for t in (1024, 512, 256, 128) if t < c and c % t == 0]


def _row_tile_options(oh: int) -> List[int]:
    """Row-band candidates: powers of two plus every divisor of OH up to
    64 (divisors waste no padded rows; 17 and 34 matter for OH=34)."""
    opts = {t for t in (1, 2, 4, 8, 16, 32) if t <= max(oh, 2)}
    opts |= {d for d in range(2, min(oh, 64) + 1) if oh % d == 0}
    return sorted(opts)


def _row_cost(oh: int, t: int) -> int:
    steps = -(-oh // t)
    return steps * t + 4 * steps            # padded rows + step overhead


def _aligned_row_tiles(geom: ConvGeom) -> Optional[set]:
    """Row-band candidates for a zero-copy fused launch (``s > 1`` with
    known final output/crop): powers of two plus divisors of
    ``ceil(OH/s)``, so ``th*s | OH`` options exist.  ``None`` for
    geometries without crop info — one definition shared by the
    heuristic and the tuner's candidate pool so they can never drift."""
    if not (geom.s > 1 and geom.out_h > 0 and geom.crop_h >= 0):
        return None
    unit = -(-geom.out_h // geom.s)         # conv rows "worth" of output
    opts = {t for t in (1, 2, 4, 8, 16, 32, 64) if t <= max(unit, 2)}
    opts |= {d for d in range(2, min(unit, 64) + 1) if unit % d == 0}
    return opts


def _pick_th(geom: ConvGeom) -> int:
    """Row band for one launch.  Zero-copy fused geometries (interleave
    ``s > 1`` with a known final output) align output tiles to the
    final geometry: a tile covers ``th*s`` output rows, so the cost is
    wasted *output* rows of the trailing partial block (plus the same
    per-step overhead proxy) — ``th*s | OH`` candidates win, which also
    skips the cropped conv rows entirely (the ``c // s`` band offset).
    Geometries without crop info keep the historical conv-row rule."""
    aligned = _aligned_row_tiles(geom)
    if aligned is not None:
        out_h, s = geom.out_h, geom.s

        def cost(t: int):
            nh = -(-out_h // (t * s))
            waste = nh * t * s - out_h      # partial trailing block
            return (waste + 4 * nh, -t)

        return min(sorted(aligned), key=cost)
    oh = geom.oh
    return min(_row_tile_options(oh),
               key=lambda t: (_row_cost(oh, t), -t))


# Per-launch VMEM budget for the footprint model: half the 16 MiB of
# scoped VMEM a Mosaic kernel gets on v5e.  The model counts the
# double-buffered blocks and the scratch at their padded (sublane,
# 128-lane) tiles; Mosaic's temporaries for the loop bodies come on top
# (the v5e compiler measured 19.05 MiB for an MDE up1 step this model
# puts at 10.5 MiB), hence the 2x headroom.
VMEM_BUDGET = 8 << 20


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def vmem_plan_bytes(geom: ConvGeom, plan: KernelPlan) -> int:
    """VMEM footprint of one grid step: input band *including the
    (K_T - 1) halo and the residual-crop row*, filter block,
    accumulator and interleaved output tile, each padded to its VMEM
    tiles, with the pipelined blocks counted twice (double buffering).

    Dtype-aware: the band and filter block are stored at the operand
    itemsize (1 byte for int8 — 4x smaller tiles-side footprint, which
    is what legalises larger (th, tw, tcin, tcout) candidates), while
    the accumulator (int32 for int8, f32 otherwise) and the dequantized
    output tile are always 4-byte.

    Algorithm-aware: a ``"wino"`` launch rounds the conv rows up to
    whole ``m``-tiles, holds the ``alpha``-per-dim transformed filter
    block and an ``alpha^2 x ntiles`` transformed-domain accumulator
    (``alpha^2/m^2`` times the direct accumulator rows), plus the f32
    ``V`` scratch of the same tile count — that is exactly why
    Winograd plans key separately from direct plans."""
    kt, ktw = geom.kt, geom.ktw or geom.kt
    s, sw = geom.s, geom.sw or geom.s
    phases = s * sw
    th = plan.th
    tw = plan.tw or geom.ow
    isz = geom.operand_itemsize
    if geom.algo == "wino":
        mh, mw = (1 if kt == 1 else 2), (1 if ktw == 1 else 2)
        ah, aw = mh + kt - 1, mw + ktw - 1
        nth = -(-(th + 1) // mh)
        ntw = -(-(tw + 1) // mw)
        lanes_in = _round_up(plan.tcin, 128)
        lanes_ph = _round_up(plan.tcout * phases, 128)
        band = ((nth * mh + kt - 1) * _round_up(ntw * mw + ktw - 1, 8)
                * lanes_in)
        filt = ah * aw * _round_up(plan.tcin, 8) * lanes_ph
        acc = ah * aw * nth * ntw * lanes_ph
        vtmp = ah * aw * nth * ntw * lanes_in
        out = (th * s * _round_up(tw * sw, 8)
               * _round_up(plan.tcout, 128))
        return 2 * (isz * (band + filt) + 4 * out) + 4 * (acc + vtmp)
    # Mirrors sd_conv.sd_fused_pallas: conv columns (residual included)
    # round up to the operand's sublane tile, the band to 8 rows, and
    # every VMEM buffer pads its channels to 128 lanes — a phase-packed
    # launch's filter block and accumulator once over all the phases'
    # lanes, an unpacked one's per phase.
    tile = 32 // isz
    cols = _round_up(tw + 1, tile)
    lanes_in, lanes_out = _round_up(plan.tcin, 128), _round_up(plan.tcout,
                                                               128)
    lanes_ph = (_round_up(phases * plan.tcout, 128)
                if phase_packed(phases, plan.tcout) else phases * lanes_out)
    band = (th + kt) * _round_up(cols + ktw - 1, tile) * lanes_in * isz
    filt = kt * ktw * _round_up(plan.tcin, tile) * lanes_ph * isz
    acc = (th + 1) * cols * lanes_ph * 4
    ytile = th * s * _round_up(tw * sw, 8) * lanes_out * 4
    # The epilogue interleaves into the f32 tile, then writes the output
    # block: 1 byte per element for chained int8 launches, else 4.
    osz = 1 if geom.qout else 4
    out = th * s * _round_up(tw * sw, 32 // osz) * lanes_out * osz
    return 2 * (band + filt + out) + acc + ytile


def _fits_budget(geom: ConvGeom, plan: KernelPlan) -> bool:
    return vmem_plan_bytes(geom, plan) <= VMEM_BUDGET


def smaller_lane_tile(c: int, t: int) -> Optional[int]:
    """The largest of :func:`lane_tiles` below ``t``, or None."""
    smaller = [u for u in lane_tiles(c) if u < t]
    return max(smaller) if smaller else None


def _smaller_col_tile(tw: int, ow: int) -> Optional[int]:
    """Next width band below ``tw`` (0 = full width): a multiple of 128,
    so both the band and the output block keep their sublane tiling."""
    t = (tw or ow) // 2 // 128 * 128
    return t if t >= 128 else None


def heuristic_plan(geom: ConvGeom) -> KernelPlan:
    """Untuned default.  Row band: minimise padded rows + a per-grid-step
    overhead proxy over :func:`_row_tile_options` (a pure power-of-two
    rule pads OH=34 by 41%; a divisor-only rule collapses to th=1 on
    prime OH — both pathological).  Width: full bands until the VMEM
    model says otherwise.  Channels: full depth unless the budget forces
    tiling of the deeper axis, in 128-lane steps (:func:`lane_tiles`)."""
    th = _pick_th(geom)
    tcin, tcout, tw = geom.cin, geom.cout, 0
    phases = geom.s * (geom.sw or geom.s)
    while not _fits_budget(geom, KernelPlan(th=th, tcin=tcin,
                                            tcout=tcout, tw=tw)):
        # Shrink the axis that buys the most: channels first (they scale
        # both the filter block and the accumulator), then the band
        # width, then the row band.
        cin_t = smaller_lane_tile(geom.cin, tcin)
        cout_t = smaller_lane_tile(geom.cout, tcout)
        tw_t = _smaller_col_tile(tw, geom.ow)
        if cin_t and (tcin >= tcout * phases or not cout_t):
            tcin = cin_t
        elif cout_t:
            tcout = cout_t
        elif tw_t:
            tw = tw_t
        elif th > 1:
            th = max(1, th // 2)
        else:
            break
    return KernelPlan(th=th, tcin=tcin, tcout=tcout, tw=tw)


def _col_tile_options(geom: ConvGeom) -> List[int]:
    """Width-band candidates: full width (0) plus 128-multiple bands
    narrower than the output — only worth searching on wide layers."""
    return [0] + [t for t in (128, 256, 512, 1024) if t < geom.ow]


def candidate_plans(geom: ConvGeom, max_candidates: int = 8,
                    enforce_budget: Optional[bool] = None
                    ) -> List[KernelPlan]:
    """Deduplicated (th, tw, tcin, tcout) search space for one geometry.

    The VMEM footprint model gates candidates **on TPU only** (a plan
    that does not fit VMEM cannot launch there); in interpret mode
    there is no VMEM and grid-step overhead dominates, so over-budget
    full-channel plans stay in the pool and *measurement* decides —
    plans are backend-gated in the cache, so a CPU winner never steers
    a TPU run anyway."""
    if enforce_budget is None:
        enforce_budget = jax.default_backend() == "tpu"
    oh = geom.oh
    base = heuristic_plan(geom)
    ths = set(_row_tile_options(oh)) - {1}
    ths |= (_aligned_row_tiles(geom) or set()) - {1}
    ths.add(base.th)
    tws = set(_col_tile_options(geom))
    tws.add(base.tw)
    cands: List[KernelPlan] = [base]
    seen = {base}
    for th in sorted(ths, reverse=True):
        for tw in sorted(tws):
            for tcin in lane_tiles(geom.cin):
                for tcout in lane_tiles(geom.cout):
                    p = KernelPlan(th=th, tcin=tcin, tcout=tcout, tw=tw)
                    if p in seen:
                        continue
                    if enforce_budget and not _fits_budget(geom, p):
                        continue
                    seen.add(p)
                    cands.append(p)
    # Rank: heuristic first, then prefer fewer grid steps (cheap proxy),
    # and cap the list so tuning stays fast.
    def steps(p: KernelPlan) -> int:
        rows = -(-oh // p.th)
        cols = -(-geom.ow // (p.tw or geom.ow))
        return (rows * cols * (geom.cin // p.tcin)
                * (geom.cout // p.tcout))

    cands.sort(key=lambda p: (p != base, steps(p)))
    return cands[:max_candidates]


# ---------------------------------------------------------------------------
# Cache persistence
# ---------------------------------------------------------------------------

def cache_path(path: Optional[str] = None) -> str:
    return path or os.environ.get(_ENV_CACHE, _DEFAULT_CACHE)


def load_cache(path: Optional[str] = None) -> Dict[str, dict]:
    p = cache_path(path)
    if p not in _MEM:
        try:
            with open(p) as f:
                data = json.load(f)
            _MEM[p] = dict(data.get("plans", {}))
        except (OSError, ValueError):
            _MEM[p] = {}
    return _MEM[p]


def save_cache(plans: Dict[str, dict], path: Optional[str] = None) -> str:
    """Atomically persist the plan cache.

    Concurrent benchmark/serve processes all write the same JSON file;
    the shared :func:`repro.core.iohelpers.atomic_write_json` idiom
    (unique mkstemp + fsync + ``os.replace``) guarantees readers only
    ever see a complete document: last writer wins, no torn files.
    """
    p = cache_path(path)
    atomic_write_json(p, {"version": 1, "plans": plans})
    _MEM[p] = dict(plans)
    return p


def _plan_from_entry(entry: dict) -> KernelPlan:
    # Pre-``tw`` cache entries measured full-width bands: tw defaults 0.
    return KernelPlan(th=int(entry["th"]), tcin=int(entry["tcin"]),
                      tcout=int(entry["tcout"]),
                      tw=int(entry.get("tw", 0)))


def get_plan(geom: ConvGeom, path: Optional[str] = None) -> KernelPlan:
    """Measured plan if the cache has one for this geometry *measured on
    the current backend*, else the heuristic.  Pure Python on static
    shapes — safe to call while jit tracing (ops.py does).

    The backend gate matters: interpret-mode CPU tuning favours plans
    that minimise interpreter overhead, which must never leak into a
    real-TPU run (and vice versa)."""
    entry = load_cache(path).get(geom.key())
    if entry is not None and entry.get("backend") == jax.default_backend():
        plan = _plan_from_entry(entry)
        if geom.cin % plan.tcin == 0 and geom.cout % plan.tcout == 0:
            return plan
    return heuristic_plan(geom)


# ---------------------------------------------------------------------------
# Measurement
# ---------------------------------------------------------------------------

def measure(fn: Callable[[], object], iters: int = 3,
            warmup: int = 1) -> float:
    """Min wall-clock milliseconds of ``fn()`` (which must block).

    Min, not mean/median: external load only ever adds time, so the
    fastest observation is the best estimator of the true kernel cost
    (classic microbenchmark practice; medians still wander badly on a
    shared machine).
    """
    for _ in range(warmup):
        fn()
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        times.append((time.perf_counter() - t0) * 1e3)
    return min(times)


def tune(geom: ConvGeom,
         runner: Callable[[KernelPlan], float],
         candidates: Optional[List[KernelPlan]] = None,
         path: Optional[str] = None,
         force: bool = False,
         cost_fn: Optional[Callable[[KernelPlan], float]] = None,
         tie_rtol: float = 0.1) -> KernelPlan:
    """Benchmark ``runner(plan) -> ms`` over the candidate set, persist
    and return the winner.  A cached measured plan short-circuits unless
    ``force``.  Candidates that raise are skipped (e.g. a tile shape the
    backend rejects); when every candidate raises, the first error is
    raised — a compile refusal never turns into a silent fallback.

    ``cost_fn`` (optional) breaks wall-clock near-ties: among plans
    within ``tie_rtol`` of the fastest, the one with the lowest cost
    wins.  ``kernel_bench`` passes the launch's ``cost_analysis``
    bytes-accessed — wall-clock on a noisy host cannot distinguish two
    tile plans 5% apart, but HBM traffic (the thing that decides on
    real hardware) can."""
    plans = dict(load_cache(path))
    key = geom.key()
    if not force:
        entry = plans.get(key)
        if (entry is not None and entry.get("source") == "measured"
                and entry.get("backend") == jax.default_backend()):
            return _plan_from_entry(entry)

    valid = [p for p in (candidates or candidate_plans(geom))
             if geom.cin % p.tcin == 0 and geom.cout % p.tcout == 0]
    # Two passes, second in reverse order: slow machine-state drift
    # (frequency scaling, allocator warmup) then biases the two ends of
    # the candidate list in opposite directions instead of crowning
    # whichever candidate ran at the quiet moment.
    best: Dict[KernelPlan, float] = {}
    first_error: Optional[Exception] = None
    for plans_pass in (valid, valid[::-1]):
        for plan in plans_pass:
            try:
                ms = runner(plan)
            except Exception as e:
                first_error = first_error or e
                continue
            best[plan] = min(ms, best.get(plan, float("inf")))
    if not best:
        if first_error is None:
            raise ValueError(f"no candidate tile divides {geom.key()}")
        raise first_error
    best_plan, best_ms = min(best.items(), key=lambda kv: kv[1])
    if cost_fn is not None:
        near = [p for p, ms in best.items()
                if ms <= best_ms * (1 + tie_rtol)]
        if len(near) > 1:
            costs: Dict[KernelPlan, float] = {}
            for p in near:
                try:
                    costs[p] = float(cost_fn(p))
                except Exception:
                    costs[p] = float("inf")
            best_plan = min(near, key=lambda p: (costs[p], best[p]))
            best_ms = best[best_plan]

    plans[key] = {**asdict(best_plan), "ms": round(best_ms, 4),
                  "source": "measured", "backend": jax.default_backend()}
    save_cache(plans, path)
    return best_plan


def measured_ms(geom: ConvGeom,
                path: Optional[str] = None) -> Optional[float]:
    """The cached measured wall-clock (ms) of ``geom``'s winning plan on
    the *current* backend, or None — the raw signal behind
    :func:`best_algo`."""
    entry = load_cache(path).get(geom.key())
    if (entry is not None and entry.get("source") == "measured"
            and entry.get("backend") == jax.default_backend()
            and entry.get("ms") is not None):
        return float(entry["ms"])
    return None


def best_algo(geom: ConvGeom, path: Optional[str] = None) -> str:
    """Measured-cost algorithm selection for one forward geometry:
    ``"wino"`` iff BOTH the direct (``algo=""``) and the Winograd
    (``algo="wino"``) variants of ``geom`` have measured entries on the
    current backend and the Winograd one is faster; ``""`` (direct)
    otherwise.  Untuned geometries never silently switch algorithm —
    the default is the exact direct kernel, and ``tune()`` runs per
    algo key (``engine.pretune`` / ``kernel_bench`` populate both)."""
    direct = measured_ms(dataclasses_replace(geom, algo=""), path)
    wino = measured_ms(dataclasses_replace(geom, algo="wino"), path)
    if direct is not None and wino is not None and wino < direct:
        return "wino"
    return ""
