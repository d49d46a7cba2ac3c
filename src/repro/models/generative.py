"""The paper's six generative benchmarks, runnable in JAX.

Every network is built from its ``NetworkSpec`` (the same spec the MAC
accounting uses, so the benchmarked FLOPs and the executed model can never
drift apart).  The deconvolution implementation is switchable and is
resolved through the executor registry (:mod:`repro.core.registry`):

    model = GenerativeModel(dcgan(), deconv_impl="sd")

``registry.names()`` lists every registered impl; unknown names raise a
``ValueError`` enumerating them with their capabilities.  Engine impls
(``sd_kernel``) run deconvs through the presplit-once SD inference
engine (:mod:`repro.engine`): filters are split into the kernel layout
and BN-folded exactly once when params are bound (at ``init``, or lazily
on the first ``apply`` with foreign params), and every forward call runs
either the *fused* Pallas kernel — split-conv, stride-s interleave, bias
and activation in one VMEM pass — or the engine's grouped-XLA execution
backend, with no splitting on the hot path either way.

Inference-time batch norm is folded into per-channel scale/bias (gamma,
beta) as any deployment on the paper's target processors would do.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import conv_nd, registry, same_deconv_pads
from repro.core.accounting import BENCHMARKS, WORKLOADS, NetworkSpec
from repro import sd

Params = Dict[str, Any]


class GenerativeModel:
    """Spec-driven generator/decoder network."""

    def __init__(self, spec: NetworkSpec, deconv_impl: str = "sd",
                 final_tanh: Optional[bool] = None,
                 engine_backend: str = "auto",
                 engine_dtype: str = "native",
                 engine_mesh=None):
        self.spec = spec
        if final_tanh is None:          # head semantics live on the spec
            final_tanh = spec.final_tanh
        self.deconv_impl = deconv_impl
        info = registry.get_impl(deconv_impl)
        if engine_dtype != "native" and not info.engine:
            raise ValueError(
                f"engine_dtype={engine_dtype!r} needs an engine impl "
                f"(e.g. 'sd_kernel'); {deconv_impl!r} is a plain "
                "executor")
        if engine_mesh is not None and not info.engine:
            raise ValueError(
                f"engine_mesh needs an engine impl (e.g. 'sd_kernel'); "
                f"{deconv_impl!r} is a plain executor")
        if info.engine:
            from repro.engine import SDEngine
            # engine_mesh: bind() Cout-shards each shardable layer's
            # split filters over the mesh's 'model' axis and keys every
            # autotune geometry per device (see SDEngine).
            self._engine: Optional["SDEngine"] = SDEngine(
                spec, backend=engine_backend, dtype=engine_dtype,
                mesh=engine_mesh)
            self._deconv = None
        else:
            self._engine = None
            self._deconv = info.fn
        self._fplans: Dict[str, Any] = {}   # geometry plans, traced path
        self.final_tanh = final_tanh

    # ---- params ----------------------------------------------------------
    def init(self, key: jax.Array, dtype=jnp.float32) -> Params:
        params: Params = {}
        keys = jax.random.split(key, len(self.spec.layers))
        for k, layer in zip(keys, self.spec.layers):
            if layer.kind == "fc":
                fan_in = layer.cin
                w = jax.random.normal(k, (layer.cin, layer.cout), dtype)
                params[layer.name] = {
                    "w": w / math.sqrt(fan_in),
                    "b": jnp.zeros((layer.cout,), dtype)}
            else:
                fan_in = layer.k ** layer.rank * layer.cin
                w = jax.random.normal(
                    k, (*(layer.k,) * layer.rank, layer.cin, layer.cout),
                    dtype)
                params[layer.name] = {
                    "w": w / math.sqrt(fan_in),
                    "b": jnp.zeros((layer.cout,), dtype),
                    "scale": jnp.ones((layer.cout,), dtype),  # folded BN
                }
        if self._engine is not None:
            # Offline phase: split + BN-fold every deconv filter exactly
            # once, here at init.  apply() never touches split_filters.
            self._engine.bind(params)
        return params

    # ---- forward ---------------------------------------------------------
    def _engine_ready(self, params: Params) -> bool:
        """True when cached engine plans are usable for these params.
        Concrete foreign params rebind the engine once; traced params
        (inside ``jit``/``grad``) take the stateless differentiable
        :func:`repro.sd.conv_transpose` path instead — caching traced
        plans would leak tracers, and the functional path is what makes
        ``sd_kernel`` trainable."""
        if self._engine is None:
            return False
        if self._engine.bound_to(params):
            return True
        if any(isinstance(leaf, jax.core.Tracer)
               for leaf in jax.tree_util.tree_leaves(params)):
            return False
        self._engine.bind(params)       # foreign params: one-time rebind
        return True

    def _functional_plan(self, layer):
        """Geometry-only DeconvPlan for the traced-params path (cached:
        it is static data, safe to reuse across traces).  Always
        ``dtype="native"``: the traced path is the differentiable
        training form, and int8 plans are inference-only — an int8
        engine still trains in float."""
        if layer.name not in self._fplans:
            act = "linear"   # act/scale/bias composed outside, like native
            self._fplans[layer.name] = self._engine.layer_plan(
                layer, act, dtype="native")
        return self._fplans[layer.name]

    def _forward(self, params: Params, x: jax.Array,
                 deconv_step) -> jax.Array:
        """The one shared layer loop.  ``deconv_step(layer, p, h) ->
        (h, epilogue_done)`` supplies the deconv strategy; everything
        else (fc matmul + reshape, conv + BN, inter-layer ReLU, final
        tanh) lives here exactly once, so every execution path — plain
        impls, cached engine plans, traced-params functional, serving
        plans-as-arguments — shares identical non-deconv semantics."""
        layers = self.spec.layers
        h = x
        for i, layer in enumerate(layers):
            p = params.get(layer.name)   # deconv steps may not need it
            last = i == len(layers) - 1
            if layer.kind == "fc":
                h = h.reshape(h.shape[0], -1)
                h = jnp.matmul(h, p["w"],
                               precision=jax.lax.Precision.HIGHEST) + p["b"]
                # reshape for the next spatial layer (any rank)
                nxt = layers[i + 1] if i + 1 < len(layers) else None
                if nxt is not None and nxt.kind != "fc":
                    h = h.reshape(h.shape[0], *nxt.in_hw, nxt.cin)
            elif layer.kind == "conv":
                pads = "SAME" if layer.padding == "same" else layer.pad
                h = conv_nd(h, p["w"], layer.s, pads)
                h = h * p["scale"] + p["b"]
            else:                        # deconv: strategy-dependent
                h, epilogue_done = deconv_step(layer, p, h)
                if epilogue_done:
                    continue
            if not last:
                h = jax.nn.relu(h)
        return jnp.tanh(h) if self.final_tanh else h

    def apply(self, params: Params, x: jax.Array) -> jax.Array:
        if self._engine_ready(params):
            # scale is folded into the cached split filters; bias and
            # the inter-layer ReLU run in the kernel/plan epilogue.
            def step(layer, p, h):
                return self._engine.run(layer.name, h), True
        elif self._engine is not None:   # traced params: differentiable
            def step(layer, p, h):
                fp = self._functional_plan(layer)
                scope = sd.current_shard_scope()
                if scope is not None:
                    # Sharded train step (sd.shard_scope active): p["w"]
                    # is this device's Cout slice, conv_transpose
                    # all-gathers the channel axis, and scale/bias are
                    # replicated — they apply to the gathered tensor.
                    n, ax = scope
                    if n > 1 and layer.cout % n == 0:
                        fp = fp.with_shards(n, ax)
                h = sd.conv_transpose(fp, h, p["w"])
                return h * p["scale"] + p["b"], False
        else:                            # plain registry executor
            def step(layer, p, h):
                pads = (same_deconv_pads((layer.k,) * layer.rank,
                                         (layer.s,) * layer.rank)
                        if layer.padding == "same" else layer.pad)
                h = self._deconv(h, p["w"], layer.s, pads)
                return h * p["scale"] + p["b"], False
        return self._forward(params, x, step)

    def apply_with_plans(self, params: Params,
                         plans: Dict[str, "sd.DeconvPlan"],
                         x: jax.Array) -> jax.Array:
        """Forward pass with the deconv layers' *bound* plans passed in
        explicitly (``engine.plans()``), instead of read from engine
        state.  Pure in all three arguments — params AND plans are
        pytrees, so the serving stack jits this once per shape and
        swaps weights/plans per call without recompiling.  ``params``
        only needs the fc/conv entries (deconv weights live pre-split
        inside the plans — the server passes the filtered dict)."""
        def step(layer, p, h):           # bias + act in the bound plan
            return sd.execute(plans[layer.name], h), True

        return self._forward(params, x, step)

    def __call__(self, params: Params, x: jax.Array) -> jax.Array:
        return self.apply(params, x)

    # ---- static activation calibration -----------------------------------
    def calibrate(self, params: Params, n: int = 64, seed: int = 0,
                  policy: str = "max", pct: float = 99.9,
                  save_key: Optional[str] = None,
                  path: Optional[str] = None,
                  latents: Optional[jax.Array] = None
                  ) -> Dict[str, float]:
        """Calibrate static per-layer activation scales for the int8
        chained path and install them on the engine.

        Runs ``n`` latents (one deterministic batch from
        ``PRNGKey(seed)`` — fixed seed => bit-identical scales) through
        the *float* functional forward and records, per deconv layer,
        the amax statistic of that layer's **input** activation
        (``policy="max"`` exact, ``"pct"`` percentile — see
        :func:`repro.core.quant.amax_stat`).  The resulting
        ``{layer: amax/127}`` scales go to
        :meth:`repro.engine.SDEngine.set_calibration`, which rebinds
        the plans with chaining wired between consecutive deconv
        layers; ``save_key`` additionally persists them to the
        calibration cache (``quant.save_calib``) next to the autotune
        plan cache, so servers can skip the sweep on warm starts.

        Pass ``latents`` to calibrate on a caller-supplied batch
        instead of unit-normal noise — static scales are only as good
        as the distribution they were swept on, so callers whose
        serving latents are scaled (or real data) should feed a
        representative batch here.
        """
        from repro.core.quant import amax_stat, save_calib, scale_from_amax
        engine = self._engine
        if engine is None or engine.dtype != "int8":
            raise ValueError("calibrate() needs an int8 engine impl "
                             "(deconv_impl='sd_kernel', "
                             "engine_dtype='int8')")
        if latents is None:
            key = jax.random.PRNGKey(seed)
            x = jax.random.normal(key, self.input_shape(int(n)),
                                  jnp.float32)
        else:
            x = jnp.asarray(latents, jnp.float32)
        stats: Dict[str, jax.Array] = {}

        def step(layer, p, h):
            # Record the layer's INPUT amax on the f32 reference path,
            # then run the float deconv (same numerics the unquantized
            # model serves) so downstream layers see faithful inputs.
            stats[layer.name] = amax_stat(h, policy, pct)
            fp = self._functional_plan(layer)
            h = sd.conv_transpose(fp, h, p["w"])
            return h * p["scale"] + p["b"], False

        self._forward(params, x, step)
        scales = {name: scale_from_amax(v) for name, v in stats.items()}
        if save_key is not None:
            save_calib(save_key, scales, path)
        engine.set_calibration(scales)
        # A never-bound engine only stored the scales above — bind now
        # (we have the params in hand) so callers see chained plans
        # immediately instead of after the first apply().
        if not engine.bound_to(params):
            engine.bind(params)
        return scales

    # ---- convenience -----------------------------------------------------
    @property
    def engine(self):
        """The SDEngine behind an engine impl (None for plain impls)."""
        return self._engine

    def input_shape(self, batch: int):
        first = self.spec.layers[0]
        if first.kind == "fc":
            return (batch, first.cin)
        return (batch, *first.in_hw, first.cin)

    def param_count(self, params: Params) -> int:
        return sum(int(np.prod(a.shape))
                   for leaf in params.values() for a in leaf.values())


def build(name: str, deconv_impl: str = "sd",
          engine_backend: str = "auto",
          engine_dtype: str = "native") -> GenerativeModel:
    """Factory: build('dcgan', 'sd') — any :data:`repro.core.accounting.
    WORKLOADS` entry (the paper's six 2-D nets plus the 1-D audio, 3-D
    voxel and segmentation workloads).  ``engine_backend`` /
    ``engine_dtype`` only matter for engine impls (see
    :class:`repro.engine.SDEngine`; ``engine_dtype="int8"`` serves the
    quantized inference path)."""
    if name not in WORKLOADS:
        raise ValueError(f"unknown workload {name!r}; choose from "
                         f"{sorted(WORKLOADS)}")
    return GenerativeModel(WORKLOADS[name](), deconv_impl=deconv_impl,
                           engine_backend=engine_backend,
                           engine_dtype=engine_dtype)


# --------------------------------------------------------------------------
# DCGAN discriminator — used by examples/train_dcgan.py (full GAN training).
# --------------------------------------------------------------------------

class DCGANDiscriminator:
    """4x4-stride-2 conv stack, LeakyReLU, logit head."""

    CHANNELS = (3, 64, 128, 256)

    def __init__(self, img_hw=(64, 64)):
        self.img_hw = img_hw

    def init(self, key, dtype=jnp.float32) -> Params:
        params: Params = {}
        ks = jax.random.split(key, len(self.CHANNELS))
        for i, (cin, cout) in enumerate(
                zip(self.CHANNELS[:-1], self.CHANNELS[1:])):
            w = jax.random.normal(ks[i], (4, 4, cin, cout), dtype)
            params[f"c{i}"] = {"w": w / math.sqrt(16 * cin),
                               "b": jnp.zeros((cout,), dtype)}
        down = 2 ** (len(self.CHANNELS) - 1)
        feat = (self.CHANNELS[-1] * (self.img_hw[0] // down)
                * (self.img_hw[1] // down))
        params["head"] = {
            "w": jax.random.normal(ks[-1], (feat, 1), dtype) / math.sqrt(feat),
            "b": jnp.zeros((1,), dtype)}
        return params

    def apply(self, params: Params, x: jax.Array) -> jax.Array:
        h = x
        for i in range(len(self.CHANNELS) - 1):
            p = params[f"c{i}"]
            h = conv_nd(h, p["w"], 2, "SAME") + p["b"]
            h = jax.nn.leaky_relu(h, 0.2)
        h = h.reshape(h.shape[0], -1)
        return h @ params["head"]["w"] + params["head"]["b"]
