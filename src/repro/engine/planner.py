"""Presplit-once SD inference engine — a plan cache over :mod:`repro.sd`.

The paper's speedup story requires the deconv -> split-conv filter
transform to be **offline**: the processor only ever executes dense
stride-1 convolutions.  Since the ``repro.sd`` redesign, the transform
itself lives in :class:`repro.sd.DeconvPlan` (a pytree: static geometry
in aux_data, split filters as leaves) — this module is the thin layer
that makes it a *serving engine*:

* :meth:`SDEngine.bind` walks a :class:`NetworkSpec` + param dict once
  and, per deconv layer, builds a **bound** plan: ``sd.plan(...)`` for
  the geometry, an autotuned ``(th, tcin, tcout)`` kernel tile from the
  JSON plan cache (:mod:`repro.kernels.autotune`), then
  ``plan.bind(w, scale, bias)`` — one ``split_filters`` call, the
  inference-BN scale folded into the split filters (a transposed conv
  is linear in its filter), the bias and inter-layer activation kept
  for the epilogue.  Plans are cached keyed to the bound params by
  *leaf identity*.
* :meth:`SDEngine.run` executes a layer through
  :func:`repro.sd.execute` using only the cached plan — no splitting,
  no BN arithmetic, no plan search on the hot path (asserted by
  tests/test_engine.py via monkeypatching).

``bind`` no longer rejects jit tracers by raising: binding is simply
skipped under a trace (caching traced plans would leak tracers across
trace boundaries), and the models route traced params through the
stateless differentiable :func:`repro.sd.conv_transpose` instead — so
``jax.jit(model.apply)(params, x)`` and training through ``sd_kernel``
both work.  Bound plans themselves are pytrees and may be passed
*through* jit as arguments (the serving stack does exactly that).
"""

from __future__ import annotations

import math
from dataclasses import replace as dataclasses_replace
from typing import Any, Dict, Iterable, Optional

import jax
import jax.numpy as jnp

from repro.core.accounting import LayerSpec, NetworkSpec
from repro.core.deconv import _ntuple, same_deconv_pads
from repro.kernels import autotune
from repro.kernels.autotune import ConvGeom, get_plan
from repro.sd import functional as sd_functional
from repro.sd.plan import (BACKENDS, DeconvPlan, plan as make_plan,
                           resolve_backend)

Params = Dict[str, Any]

# Activations the split-deconv epilogues apply (the fused kernel's,
# and the XLA backend's after its conv).
EPILOGUE_ACTS = ("linear", "relu")

# Engine plans ARE repro.sd plans now; the old name survives for callers
# that predate the repro.sd split (tests, benchmarks, introspection).
LayerPlan = DeconvPlan


def fold_scale_ocmajor(ws_ocmajor: jax.Array, scale: jax.Array,
                       s) -> jax.Array:
    """Fold a per-output-channel scale into oc-major split filters,
    any rank.

    oc-major channel c = oc*phases + phase, so each scale entry covers
    ``phases = prod(s)`` consecutive phase channels — ``s^d`` for the
    rank ``d`` implied by the filter array (``ws.ndim - 2``), not the
    2-D-only ``s*s`` this helper used to hardcode.  ``s`` may be an int
    (hypercubic) or a per-dim stride tuple.
    """
    rank = ws_ocmajor.ndim - 2
    phases = math.prod(_ntuple(s, rank))
    return ws_ocmajor * jnp.repeat(scale.astype(ws_ocmajor.dtype),
                                   phases)


class SDEngine:
    """Per-network cache of presplit, BN-folded, tile-planned deconvs.

    ``backend`` selects how the cached plans execute: ``"fused"`` runs
    the direct Pallas kernel (the TPU deployment path; interpret mode
    off-TPU) — and, once :meth:`pretune` has measured both algorithm
    variants of a layer geometry, auto-switches individual layers to
    the Winograd fast-algorithm kernel where it measured faster (see
    :meth:`_layer_backend`); ``"winograd"`` pins the fast algorithm on
    every layer; ``"xla"`` runs the grouped stride-1 conv +
    pixel-shuffle from the same presplit filters (the fast off-TPU
    serving path); ``"auto"`` picks fused on TPU and xla elsewhere.
    The offline phase is the same split + BN fold per layer at bind —
    winograd plans additionally fold the ``G g G^T`` filter transform
    there.

    ``dtype="int8"`` builds quantized plans: bind() additionally
    quantizes the scale-folded split filters per output channel, and
    the hot path runs int8 activations with the dequant epilogue (see
    :mod:`repro.core.quant`).  Plan-cache/jit keys include the dtype,
    so one process can serve float and int8 engines side by side.

    :meth:`set_calibration` installs static per-layer activation
    scales on an int8 engine (from ``GenerativeModel.calibrate`` or the
    on-disk calibration cache): every calibrated layer quantizes its
    input statically (no per-sample amax on the hot path), and each
    pair of *consecutive* deconv layers chains — layer i's epilogue
    folds ``1/sx_{i+1}`` and re-quantizes to int8 in VMEM, so the
    inter-layer tensor crosses HBM as int8.  An intervening non-deconv
    layer (segnet's mid-net conv) breaks the chain there; the first
    layer quantizes its f32 input statically and the last keeps f32
    output (tanh does not commute with the scale).  Chained layers'
    tiles key under ``_q8out`` (their output tile is 4x smaller in
    VMEM).
    """

    def __init__(self, spec: NetworkSpec, plan_batch: int = 1,
                 backend: str = "fused", dtype: str = "native",
                 mesh=None, dp_axis: str = "data",
                 mp_axis: str = "model"):
        from repro.sd.plan import DTYPES
        if dtype not in DTYPES:
            raise ValueError(f"unknown engine dtype {dtype!r}; "
                             f"choose from {DTYPES}")
        self.spec = spec
        self.plan_batch = plan_batch     # batch used for plan-cache keys
        self.backend = resolve_backend(backend)
        self.dtype = dtype
        # Mesh-aware engine: ``mesh`` (a (data, model) jax Mesh) makes
        # bind() place each shardable layer's split filters Cout-sharded
        # over ``mp_axis`` via NamedSharding, and makes every autotune
        # geometry — hence tile keys AND estimate_ms — describe what one
        # device actually launches: the per-device batch slice over
        # ``dp_axis`` and the per-shard Cout slice over ``mp_axis``.
        self.mesh = mesh
        self.dp_axis, self.mp_axis = dp_axis, mp_axis
        if mesh is not None:
            self.dp = (int(mesh.shape[dp_axis])
                       if dp_axis in mesh.axis_names else 1)
            self.mp = (int(mesh.shape[mp_axis])
                       if mp_axis in mesh.axis_names else 1)
        else:
            self.dp = self.mp = 1
        self._plans: Dict[str, DeconvPlan] = {}
        self._bound: Optional[Params] = None
        self._bound_leaves: Optional[tuple] = None
        self._calib: Optional[Dict[str, float]] = None

    def _layer_shards(self, layer: LayerSpec) -> int:
        """Cout shards this engine gives one layer: the mesh's model
        degree when it divides the layer's output channels, else 1 —
        narrow final layers (cout 3 or 1) replicate rather than forcing
        the whole net off the mesh."""
        if self.mp > 1 and layer.cout % self.mp == 0:
            return self.mp
        return 1

    def _plan_leaves(self, params: Params) -> Optional[tuple]:
        """The leaves the plans depend on, compared by *object identity*
        at bound_to time.  jax arrays are immutable, so replacing a value
        always breaks identity; the container dicts are deliberately NOT
        part of the fingerprint — a rebuilt pytree holding the same
        arrays (``{**params}``, device_put of the same buffers) must
        reuse the plans, while in-place mutation of a bound dict
        (``params['d1']['w'] = new_w``) must invalidate them.  The bound
        leaves are held strongly (not as ``id()`` ints) so CPython id
        reuse after garbage collection can never alias two different
        arrays."""
        leaves = []
        for layer in self.spec.layers:
            if layer.kind != "deconv":
                continue
            p = params.get(layer.name)
            if not isinstance(p, dict) or "w" not in p:
                return None
            leaves += [p["w"], p.get("scale"), p.get("b")]
        return tuple(leaves)

    # ---- offline phase ---------------------------------------------------
    def _layer_backend(self, layer: LayerSpec, dtype: str,
                       geom: Optional[ConvGeom]) -> str:
        """Execution backend for one layer — where the autotuner becomes
        an *algorithm* selector, not just a tile picker.  A ``"fused"``
        engine consults :func:`autotune.best_algo` per layer geometry:
        if BOTH the direct and the Winograd variants have measured plan
        entries on the current backend (``pretune``/``kernel_bench``
        populate them) and Winograd measured faster, the layer binds a
        winograd plan instead.  Untuned layers never silently switch —
        the default stays the exact direct kernel.  Engines constructed
        with ``backend="winograd"`` pin the fast algorithm on every
        layer (and raise at plan() time for unsupported geometry)."""
        if (self.backend != "fused" or dtype == "int8" or geom is None
                or layer.rank != 2):
            return self.backend
        from repro.kernels.winograd import supported
        kt = -(-layer.k // layer.s)
        if not supported((kt, kt)):
            return self.backend
        if autotune.best_algo(geom) == "wino":
            return "winograd"
        return self.backend

    def layer_plan(self, layer: LayerSpec, act: str,
                   dtype: Optional[str] = None,
                   qout: bool = False) -> DeconvPlan:
        """Geometry-only plan for one deconv layer: split layout +
        autotuned kernel tile, no filter data.  Static and trace-safe.
        Rank follows the layer's input spatial shape (1-D/2-D/3-D);
        autotuned tiles exist for the 2-D kernel geometry — other ranks
        resolve their tile at call time from the lowered geometry.
        ``dtype`` overrides the engine dtype (the models' traced
        training path requests "native" plans from an int8 engine —
        int8 plans are inference-only).  On a ``"fused"`` engine the
        per-layer compute algorithm is measured-cost selected (see
        :meth:`_layer_backend`); tile lookup then uses the matching
        ``algo``-tagged plan-cache key."""
        rank = layer.rank
        kernel = (layer.k,) * rank
        stride = (layer.s,) * rank
        pads = (same_deconv_pads(kernel, stride)
                if layer.padding == "same" else layer.pad)
        dtype = self.dtype if dtype is None else dtype
        tile = None
        geom = self.layer_geom(layer, dtype=dtype, qout=qout)
        backend = self._layer_backend(layer, dtype, geom)
        if geom is not None:
            if backend == "winograd":
                geom = dataclasses_replace(geom, algo="wino")
            tile = get_plan(geom)
        return make_plan(
            (*kernel, layer.cin, layer.cout), stride, pads,
            backend=backend, act=act, tile=tile, dtype=dtype)

    def _chain_next(self) -> Dict[str, str]:
        """Chaining wiring from the installed calibration: maps each
        deconv layer's name to the *next* layer's name when the two are
        consecutive in the spec, both deconv, both calibrated, and the
        first's output reaches the second alone and unchanged — no norm
        or join between them (``NetworkSpec.plain_edge``): the pairs
        whose inter-layer tensor crosses HBM as int8.  Their epilogue
        activation is linear or relu, both fold-compatible; the last
        layer has no successor, so it never chains out (its f32 output
        feeds the model-level tanh)."""
        out: Dict[str, str] = {}
        if not self._calib or self.dtype != "int8":
            return out
        layers = self.spec.layers
        for i, layer in enumerate(layers[:-1]):
            nxt = layers[i + 1]
            if (layer.kind == "deconv" and nxt.kind == "deconv"
                    and layer.name in self._calib
                    and nxt.name in self._calib
                    and self.spec.plain_edge(i)):
                out[layer.name] = nxt.name
        return out

    def build_plans(self, params: Params) -> Dict[str, DeconvPlan]:
        """Bound plans for every deconv layer — pure (no engine-state
        mutation), so it also works on traced params inside a jit: the
        resulting plans are pytrees of the trace's tracers.  With
        calibration installed (int8 engines), plans pick up static
        ``sx_in`` scales and consecutive deconv pairs chain (see
        :meth:`set_calibration`)."""
        layers = self.spec.layers
        calib = self._calib if self.dtype == "int8" else None
        chain_next = self._chain_next()
        plans: Dict[str, DeconvPlan] = {}
        for i, layer in enumerate(layers):
            if layer.kind != "deconv":
                continue
            p = params[layer.name]
            shards = self._layer_shards(layer)
            tgt = chain_next.get(layer.name)
            act = self.spec.epilogue_act(i)
            if act not in EPILOGUE_ACTS:
                act = "linear"              # the model applies it after
            bias = p.get("b")
            bound = self.layer_plan(layer, act, qout=tgt is not None).bind(
                p["w"], scale=p.get("scale"),
                bias=None if bias is None else bias.astype(jnp.float32),
                mesh=self.mesh if shards > 1 else None,
                axis=self.mp_axis)
            if (self.mesh is not None and shards == 1
                    and not isinstance(bound.ws, jax.core.Tracer)):
                # Replicated layers live on every mesh device too, so a
                # launch moves no filter bytes between devices.
                bound = bound.shard_put(self.mesh)
            if calib and layer.name in calib:
                bound = bound.with_chain(
                    sx_in=calib[layer.name],
                    sx_out=calib[tgt] if tgt is not None else None,
                    chain_out=tgt is not None)
            plans[layer.name] = bound
        return plans

    def set_calibration(
            self, scales: Optional[Dict[str, float]]) -> "SDEngine":
        """Install static per-layer activation scales — ``{layer name:
        input amax/127}`` as produced by ``GenerativeModel.calibrate``
        or loaded from the calibration cache (``quant.load_calib``).
        Rebinds in place when params are already bound, so
        ``swap_checkpoint -> engine.bind`` keeps the calibration: the
        new plans carry the same scale leaves and the chained jit cache
        entries are reused without retrace.  ``None`` clears
        calibration (back to dynamic per-sample scales)."""
        if scales is not None and self.dtype != "int8":
            raise ValueError("calibration applies to int8 engines only")
        self._calib = dict(scales) if scales is not None else None
        if self._bound is not None:
            self.bind(self._bound)
        return self

    def bind(self, params: Params) -> "SDEngine":
        """Build and cache all layer plans from ``params`` (called once
        per param set — at model init, or lazily on the first apply with
        foreign params).  The old blanket under-jit rejection is gone —
        concrete params bind fine inside a trace (plans stay concrete)
        — but *traced* params still raise: caching tracers would leak
        them across trace boundaries and silently serve stale weights.
        Traced params belong on the stateless
        ``repro.sd.conv_transpose`` path (``models.generative`` routes
        them there automatically)."""
        leaves = self._plan_leaves(params)
        if leaves is not None and any(
                isinstance(l, jax.core.Tracer) for l in leaves):
            raise ValueError(
                "SDEngine.bind called with traced params; the engine "
                "caches concrete plans — use repro.sd.conv_transpose "
                "for traced params (GenerativeModel.apply does this "
                "automatically under jit/grad)")
        self._plans = self.build_plans(params)
        self._bound = params
        self._bound_leaves = leaves
        return self

    def bound_to(self, params: Params) -> bool:
        if self._bound is None or self._bound_leaves is None:
            return False
        leaves = self._plan_leaves(params)
        return (leaves is not None
                and len(leaves) == len(self._bound_leaves)
                and all(a is b for a, b in
                        zip(leaves, self._bound_leaves)))

    # ---- batch-aware tiles ----------------------------------------------
    def layer_geom(self, layer: LayerSpec,
                   batch: Optional[int] = None,
                   dtype: Optional[str] = None,
                   algo: str = "",
                   qout: bool = False) -> Optional[ConvGeom]:
        """Autotune geometry of one deconv layer's fused launch at
        ``batch`` (defaults to ``plan_batch``).  Rank-2 only — the 1-D
        and 3-D lowerings resolve their tiles at call time from the
        lowered geometry.  Int8 engines tag the geometry, so their
        plans are keyed (and their VMEM footprint modelled) for 1-byte
        operands; ``algo="wino"`` tags the Winograd variant of the same
        launch (separate cache key + transformed-tile footprint).

        On a mesh engine the geometry is what ONE DEVICE launches:
        ``batch`` is divided (ceil) over the data degree and ``cout``
        over the layer's shard count, with ``shards`` tagged into the
        key — so tiles, measurements and :meth:`estimate_ms` can never
        be wrong by the parallelism factor, and an MP-measured entry
        (which includes its all-gather) never steers a same-local-shape
        unsharded layer."""
        if layer.rank != 2:
            return None
        pads = (same_deconv_pads(layer.k, layer.s)
                if layer.padding == "same" else layer.pad)
        dtype = self.dtype if dtype is None else dtype
        b = batch or self.plan_batch
        b = max(1, -(-b // self.dp))
        shards = self._layer_shards(layer)
        geom = ConvGeom.from_deconv(b, *layer.in_hw, layer.cin,
                                    layer.cout // shards,
                                    layer.k, layer.s, padding=pads,
                                    dtype="int8" if dtype == "int8"
                                    else "")
        if shards > 1:
            geom = dataclasses_replace(geom, shards=shards)
        if qout:
            # Chained launch: int8 output tile — separate plan-cache
            # key (``_q8out``) and a 4x smaller output in the footprint.
            geom = dataclasses_replace(geom, qout=True)
        return dataclasses_replace(geom, algo=algo) if algo else geom

    def plans_for_batch(self, batch: int) -> Dict[str, DeconvPlan]:
        """The cached bound plans with tiles re-resolved for ``batch``.

        A plan's tile is part of its static geometry, and the tile that
        wins at ``plan_batch=1`` is generally wrong at batch 16 — this
        is what lets the bucketed serving stack key tiles to the bucket
        it actually launches instead of silently reusing the bind-time
        batch (re-tiling shares the split filter arrays; nothing is
        re-split)."""
        if batch == self.plan_batch:
            return self.plans()
        layers = {l.name: l for l in self.spec.layers
                  if l.kind == "deconv"}
        out: Dict[str, DeconvPlan] = {}
        for name, plan in self._plans.items():
            geom = self.layer_geom(
                layers[name], batch,
                algo="wino" if plan.backend == "winograd" else "",
                qout=plan.chain_out)
            out[name] = (plan if geom is None
                         else plan.with_tile(get_plan(geom)))
        return out

    def pretune(self, batches: Iterable[int], iters: int = 3,
                path: Optional[str] = None) -> Dict[str, Any]:
        """Measure-and-cache tile plans for every (deconv layer, batch)
        geometry in ``batches`` — the serving warm-up behind
        ``serve_gen --pretune``.  Runs the real presplit hot path
        (:func:`repro.sd.execute`) per candidate, so it needs bound
        plans.  Tile plans only steer the Pallas backends (fused /
        winograd); on xla this is a no-op.

        A float ``"fused"`` engine additionally tunes the **Winograd
        variant** of every supported layer geometry (the bound oc-major
        split filters pass through the offline ``G g G^T`` transform
        here, nothing is re-split) — populating both ``algo`` cache
        keys is what arms :func:`autotune.best_algo`, and the engine
        re-binds afterwards so layers where the fast algorithm measured
        faster switch to winograd plans immediately.  Returns
        ``{geom key: winning KernelPlan}``."""
        tuned: Dict[str, Any] = {}
        if self.backend not in ("fused", "winograd"):
            return tuned
        if not self._plans:
            raise ValueError("pretune() needs bound plans; bind() first")
        from repro.kernels.winograd import supported, transform_filters
        layers = {l.name: l for l in self.spec.layers
                  if l.kind == "deconv"}

        def tune_variant(plan, layer, b, x):
            algo = "wino" if plan.backend == "winograd" else ""
            geom = self.layer_geom(layer, b, algo=algo,
                                   qout=plan.chain_out)

            def runner(tile, _x=x, _plan=plan):
                p2 = _plan.with_tile(tile)
                if self.mesh is not None:
                    # Sharded plans gather over a mesh axis: measure the
                    # real SPMD launch (collective included) so the tile
                    # that wins is the one serving actually runs.
                    fn = jax.jit(lambda pp, xx: sd_functional.execute_spmd(
                        pp, xx, self.mesh, dp_axis=self.dp_axis))
                else:
                    fn = jax.jit(sd_functional.execute)
                return autotune.measure(
                    lambda: jax.block_until_ready(fn(p2, _x)),
                    iters=iters)

            tuned[geom.key()] = autotune.tune(geom, runner, path=path)

        for name, plan in self._plans.items():
            layer = layers[name]
            if self.layer_geom(layer) is None:
                continue                       # rank 1/3: call-time tiles
            # Int8 plans store int8 filters but execute() takes float
            # activations (it quantizes per sample in-trace).
            dtype = (plan.ws.dtype
                     if plan.ws is not None and plan.dtype != "int8"
                     else jnp.float32)
            variants = [plan]
            # The Winograd kernel does not lower with Mosaic (ROADMAP
            # S7): on TPU only the direct kernel is a candidate.
            if (self.backend == "fused" and plan.backend == "fused"
                    and plan.dtype != "int8" and supported(plan.kt)
                    and jax.default_backend() != "tpu"):
                variants.append(dataclasses_replace(
                    plan, backend="winograd", layout="wino",
                    ws=transform_filters(plan.ws)))
            for b in sorted({int(x) for x in batches}):
                x = jnp.zeros((b, *layer.in_hw, layer.cin), dtype)
                for v in variants:
                    tune_variant(v, layer, b, x)
        if self.backend == "fused" and self._bound is not None:
            # Re-resolve per-layer algorithms against the fresh
            # measurements (bind is cheap next to the tuning sweep).
            self.bind(self._bound)
        return tuned

    # ---- service-time model ---------------------------------------------
    def estimate_ms(self, batch: int) -> Optional[float]:
        """Estimated wall-clock (ms) of one forward pass at ``batch``,
        summed from the autotuner's *measured* per-layer plan entries
        for this engine's launch geometries (``pretune``/``kernel_bench``
        populate them) — the cold-start seed for the serving
        scheduler's admission control.  ``batch`` is the *global*
        launch bucket; on a mesh engine :meth:`layer_geom` keys the
        lookup on what one device runs (per-device batch slice,
        per-shard Cout, ``_mp`` suffix) — a DP=4 engine's estimate is
        the batch/4 measurement, not the 4x-wrong global one.  Honest about ignorance: None
        unless **every** deconv layer has a measured entry on the
        current backend (rank 1/3 layers resolve tiles at call time
        and carry no measured entries), and a floor by construction —
        fc/conv layers and dispatch overhead are not modelled.  The
        scheduler's observed-launch EWMA takes over from the first real
        launch."""
        total = 0.0
        for name, plan in self._plans.items():
            layer = next(l for l in self.spec.layers if l.name == name)
            geom = self.layer_geom(
                layer, batch,
                algo="wino" if plan.backend == "winograd" else "",
                qout=plan.chain_out)
            if geom is None:
                return None
            ms = autotune.measured_ms(geom)
            if ms is None:
                return None
            total += ms
        return total

    # ---- hot path --------------------------------------------------------
    def run(self, name: str, x: jax.Array) -> jax.Array:
        """Deconv + folded BN + activation for layer ``name`` from the
        cached plan.  Touches nothing offline on either backend."""
        return sd_functional.execute(self._plans[name], x)

    # ---- introspection ---------------------------------------------------
    def plans(self) -> Dict[str, DeconvPlan]:
        return dict(self._plans)

    def describe(self) -> str:
        mesh = (f" mesh=dp{self.dp}xmp{self.mp}"
                if self.mesh is not None else "")
        lines = [f"SDEngine[{self.spec.name}] backend={self.backend} "
                 f"dtype={self.dtype}{mesh} "
                 f"({len(self._plans)} deconv layers)"]
        for name, plan in self._plans.items():
            kt = -(-plan.kernel[0] // plan.s)
            tile = (f"tile=(th={plan.tile.th}, tw={plan.tile.tw}, "
                    f"tcin={plan.tile.tcin}, tcout={plan.tile.tcout})"
                    if plan.tile is not None else "tile=call-time")
            sh = (f" shards={plan.shards}@{plan.shard_axis}"
                  if plan.shards > 1 else "")
            lines.append(
                f"  {name}: rank={plan.rank} K={plan.kernel[0]} "
                f"s={plan.s} KT={kt} act={plan.act} "
                f"backend={plan.backend}{sh} {tile}")
        return "\n".join(lines)
