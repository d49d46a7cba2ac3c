"""DeconvPlan: the split-deconvolution layout as a jit-crossable pytree.

The paper's transform has two halves: a *static* geometry (how a
(K, s, padding) deconv decomposes into ``prod(s)`` stride-1 sub-filters
of ``K_T = ceil(K/s)`` taps, and where the pixel-shuffled output is
cropped) and the *filter data* laid out for that geometry.  This module
keeps them in one frozen dataclass registered as a JAX pytree:

* the geometry — kernel, stride, padding, output_padding, channel
  counts, execution backend, epilogue activation, filter layout and
  (optionally) the autotuned kernel tile — is **aux_data**: hashable,
  compared by value, and therefore part of the jit cache key, exactly
  like static_argnums.  The spatial **rank** (1, 2 or 3) is carried by
  the kernel/stride tuples themselves, so it keys the cache too;
* the filter arrays of a *bound* plan (``ws``: the pre-split filters,
  with any folded per-channel scale; ``bias``) are **leaves**, so a
  bound plan crosses ``jit`` / ``grad`` / ``shard_map`` boundaries as a
  plain argument — no tracer rejection, no closure capture, and weight
  updates never force a retrace.

``plan()`` builds an unbound (geometry-only) plan; ``DeconvPlan.bind``
splits a filter once and returns a bound plan.  The runtime entry
points live in :mod:`repro.sd.functional`.
"""

from __future__ import annotations

import math
import threading
from contextlib import contextmanager
from dataclasses import dataclass, replace
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.deconv import (_check_output_padding, _check_padding,
                               _ntuple, _pads_nd, deconv_output_shape,
                               sd_geometry, split_filters, unsplit_filters)
from repro.kernels.autotune import KernelPlan

BACKENDS = ("fused", "xla", "winograd")
LAYOUTS = ("nmajor", "ocmajor", "wino")

# Execution strategy of the "fused" backend per spatial rank: ranks 1-2
# run the fused Pallas kernel directly (1-D lowers as an H=1 2-D call);
# rank 3 folds depth into batch for the intra-slice Pallas convs and
# falls back to grouped-XLA layout ops for the cross-slice interleave
# (see functional._run_presplit; the registry's per-rank ``backends``
# capability metadata records the same strategy).


def resolve_backend(backend: str) -> str:
    """'fused' = the direct Pallas kernel (interpret mode off-TPU);
    'winograd' = the fast-algorithm Pallas kernel (F(2,r) minimal
    filtering on the stride-1 subfilters, ranks 1-2, taps <= 5, float
    only); 'xla' = the grouped stride-1 conv + pixel-shuffle; 'auto'
    picks per jax backend."""
    if backend == "auto":
        return "fused" if jax.default_backend() == "tpu" else "xla"
    if backend not in BACKENDS:
        raise ValueError(f"unknown SD backend {backend!r}; "
                         f"choose from {('auto',) + BACKENDS}")
    return backend


def to_shardblocked(ws: jax.Array, s, shards: int,
                    phases: Optional[int] = None) -> jax.Array:
    """Permute n-major split filters so that a contiguous 1/``shards``
    slice of the channel axis is itself n-major over a Cout block.

    Plain n-major order (channel ``c = phase*Cout + oc``) interleaves
    every device's output channels across the phase blocks, so a
    contiguous ``NamedSharding`` slice would mix phases.  Shard-blocked
    order is ``c = shard*(phases*Coutl) + phase*Coutl + ocl`` — device
    ``d``'s slice is exactly the n-major layout of its own Cout block,
    so the per-device kernel body needs no relayout at all.  (oc-major
    and wino layouts are already contiguous per Cout block.)"""
    rank = ws.ndim - 2
    if phases is None:
        phases = math.prod(_ntuple(s, rank))
    kt = ws.shape[:rank]
    cin, nc = ws.shape[rank], ws.shape[rank + 1]
    coutl = nc // phases // shards
    w = ws.reshape(*kt, cin, phases, shards, coutl)
    return jnp.swapaxes(w, -2, -3).reshape(*kt, cin, nc)


def to_ocmajor(ws: jax.Array, s, phases: Optional[int] = None) -> jax.Array:
    """Relayout split filters from n-major (what ``depth_to_space``
    consumes) to oc-major (what the fused Pallas kernel consumes),
    any rank.  ``s`` is the per-dim stride (int or tuple); ``phases``
    overrides the phase count (defaults to ``prod(s)`` over the rank
    inferred from ``ws``)."""
    rank = ws.ndim - 2
    if phases is None:
        phases = math.prod(_ntuple(s, rank))
    kt = ws.shape[:rank]
    cin, nc = ws.shape[rank], ws.shape[rank + 1]
    cout = nc // phases
    w = ws.reshape(*kt, cin, phases, cout)
    return jnp.swapaxes(w, -1, -2).reshape(*kt, cin, cout * phases)


@dataclass(frozen=True)
class DeconvPlan:
    """Split layout of one transposed convolution, any spatial rank.

    Static geometry (pytree aux_data): ``kernel``, ``stride``,
    ``padding`` (normalised to ``((lo, hi),) * rank``),
    ``output_padding``, ``cin``, ``cout``, ``backend``, ``act``,
    ``layout``, ``tile``, ``dtype``.  ``rank == len(kernel)``.

    Leaves (only set on a *bound* plan): ``ws`` — the pre-split filters
    in ``layout`` order with any per-channel scale folded in — and
    ``bias``.  An int8 plan (``dtype="int8"``) additionally carries
    ``wscale``, the per split-output-channel dequant scales (same
    channel order as ``ws``); its ``ws`` holds int8 values with the BN
    scale folded into ``wscale`` instead of the filter data.

    ``dtype`` is aux_data, so float and int8 bindings of the same layer
    hash to *different* jit cache entries — a server can hold both
    without retrace collisions.

    Activation chaining (static calibration): ``sx_in`` / ``sx_out``
    are optional scalar f32 **leaves** — the calibrated static
    activation scales of this layer's input and output.  With ``sx_in``
    set, execution quantizes the f32 input statically (no per-sample
    amax pass) — or consumes an int8 input directly.  ``chain_out``
    (**aux**, it decides the launch's output dtype) marks the epilogue
    to fold ``1/sx_out`` into the dequant scale + bias and re-quantize
    the activated tile to int8 in VMEM, so the inter-layer tensor lives
    in HBM as int8 and the next layer's plan (whose ``sx_in`` ==
    ``sx_out``) consumes it with no round-trip.  The scales are leaves
    so recalibration / checkpoint swap never retraces.
    """
    kernel: Tuple[int, ...]
    stride: Tuple[int, ...]
    padding: Tuple[Tuple[int, int], ...]
    cin: int
    cout: int
    backend: str = "xla"
    act: str = "linear"                    # "linear" | "relu" | "tanh"
    layout: str = "nmajor"
    tile: Optional[KernelPlan] = None      # autotuned (th, tw, tcin, tcout)
    output_padding: Tuple[int, ...] = None  # normalised in plan()
    dtype: str = "native"                  # "native" | "int8"
    shards: int = 1                        # Cout shards over shard_axis
    shard_axis: str = "model"              # mesh axis name of the shards
    chain_out: bool = False                # aux: epilogue requantizes to int8
    ws: Optional[jax.Array] = None         # leaf: pre-split filters
    bias: Optional[jax.Array] = None       # leaf: per-oc bias
    wscale: Optional[jax.Array] = None     # leaf: int8 per-channel scales
    sx_in: Optional[jax.Array] = None      # leaf: static input act scale
    sx_out: Optional[jax.Array] = None     # leaf: static output act scale

    def __post_init__(self):
        if self.output_padding is None:
            object.__setattr__(self, "output_padding",
                               (0,) * len(self.kernel))

    # ---- derived geometry ------------------------------------------------
    @property
    def rank(self) -> int:
        """Spatial rank (1, 2 or 3) — implied by the kernel tuple, so it
        is part of aux_data and keys the jit cache."""
        return len(self.kernel)

    @property
    def s(self) -> int:
        """Hypercubic stride as an int (the fused kernel requires it)."""
        if len(set(self.stride)) != 1:
            raise ValueError(f"non-square stride {self.stride}")
        return self.stride[0]

    @property
    def phases(self) -> int:
        """Number of split sub-filters, prod(s) over the rank."""
        return math.prod(self.stride)

    @property
    def kt(self) -> Tuple[int, ...]:
        return sd_geometry(self.kernel, self.stride)[0]

    @property
    def pk(self) -> Tuple[int, ...]:
        return sd_geometry(self.kernel, self.stride)[1]

    @property
    def pi(self) -> Tuple[int, ...]:
        return sd_geometry(self.kernel, self.stride)[2]

    def out_shape(self, in_space: Sequence[int]) -> Tuple[int, ...]:
        return deconv_output_shape(in_space, self.kernel, self.stride,
                                   self.padding, self.output_padding)

    @property
    def bound(self) -> bool:
        return self.ws is not None

    # Legacy LayerPlan field names (engine tests and introspection).
    @property
    def ws_ocmajor(self) -> Optional[jax.Array]:
        return self.ws if self.layout == "ocmajor" else None

    @property
    def ws_nmajor(self) -> Optional[jax.Array]:
        return self.ws if self.layout == "nmajor" else None

    # ---- binding ---------------------------------------------------------
    def _bound_layout(self) -> str:
        """The filter layout this plan's execution path consumes:
        oc-major for the fused Pallas kernel (ranks 1-2); n-major for
        XLA and for the rank-3 fused lowering (its interleave is the
        XLA ``depth_to_space``)."""
        if self.backend == "winograd":
            return "wino"
        if self.backend == "fused" and self.rank <= 2:
            return "ocmajor"
        return "nmajor"

    @property
    def cout_local(self) -> int:
        """Output channels each shard computes (== cout when unsharded)."""
        return self.cout // self.shards

    def with_shards(self, shards: int,
                    axis: Optional[str] = None) -> "DeconvPlan":
        """Mark this plan as Cout-sharded ``shards`` ways over mesh axis
        ``axis``.  Geometry-only marking: inside ``shard_map`` each
        device then runs its 1/``shards`` Cout slice and ``execute`` /
        ``conv_transpose`` all-gather the channel axis in the epilogue.
        Binding with ``mesh=`` sets this automatically."""
        shards = int(shards)
        if shards < 1:
            raise ValueError(f"shards must be >= 1, got {shards}")
        if shards > 1 and self.cout % shards:
            raise ValueError(
                f"cout {self.cout} not divisible by {shards} shards")
        # A tile resolved for another Cout need not divide this one's
        # shard: re-resolve at launch from the local geometry.
        tile = self.tile if shards == self.shards else None
        return replace(self, shards=shards, tile=tile,
                       shard_axis=self.shard_axis if axis is None
                       else str(axis))

    def shard_specs(self, P=None) -> "DeconvPlan":
        """This plan's pytree with each array leaf replaced by its
        ``PartitionSpec`` — ``ws`` sharded over its channel (last) axis,
        ``bias``/``wscale`` over their only axis, everything replicated
        when ``shards == 1``.  Feed directly to ``shard_map`` in_specs
        (the plan's aux_data rides along in the treedef) or zip with the
        leaves for ``NamedSharding`` placement."""
        if P is None:
            from jax.sharding import PartitionSpec as P
        ax = self.shard_axis if self.shards > 1 else None
        leaves = []
        if self.ws is not None:
            leaves.append(P(*(None,) * (self.ws.ndim - 1), ax))
        if self.bias is not None:
            leaves.append(P(ax))
        if self.wscale is not None:
            leaves.append(P(ax))
        if self.sx_in is not None:          # scalar scales: replicated
            leaves.append(P())
        if self.sx_out is not None:
            leaves.append(P())
        return jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(self), leaves)

    def shard_put(self, mesh) -> "DeconvPlan":
        """Place a bound plan's leaves on ``mesh`` via ``NamedSharding``
        per :meth:`shard_specs` — each device materialises only its Cout
        slice of the split filters (and bias/``wscale``)."""
        from jax.sharding import NamedSharding
        return jax.tree_util.tree_map(
            lambda arr, spec: jax.device_put(
                arr, NamedSharding(mesh, spec)),
            self, self.shard_specs())

    def bind(self, w: jax.Array, scale: Optional[jax.Array] = None,
             bias: Optional[jax.Array] = None,
             act: Optional[str] = None,
             mesh=None, axis: str = "model") -> "DeconvPlan":
        """Split ``w`` once (the paper's offline transform) and return a
        bound plan.  ``scale`` (folded inference-BN gamma/sqrt(var)) is
        multiplied into the split filters — a deconv is linear in its
        filter, so scaling filter output-channels == scaling the output.
        The filters are stored in the layout this plan's backend
        consumes (oc-major for the fused kernel, n-major for XLA).

        ``dtype="int8"`` plans quantize the scale-folded split filters
        here, per split output channel (symmetric, amax/127): the BN
        fold happens *first* on the f32 filters, then quantization —
        so the per-channel ``wscale`` absorbs both the filter magnitude
        and the BN gamma, exactly the one-multiply epilogue the fused
        kernel runs.  The stored ``ws`` is int8; ``wscale`` follows the
        same (oc-major or n-major) channel order as ``ws``.

        ``mesh`` (a ``jax.sharding.Mesh``) requests a Cout-sharded
        binding: the split filters (and ``wscale``/``bias``) are
        relaid so a contiguous slice over the channel axis is one
        device's Cout block, then placed with ``NamedSharding`` over
        mesh axis ``axis`` — each device holds only its slice.  The
        bound plan records ``shards``/``shard_axis`` in aux_data, and
        ``execute`` all-gathers the channel axis when run under
        ``shard_map``.  Requires ``cout % mesh.shape[axis] == 0``.
        """
        if w.shape != (*self.kernel, self.cin, self.cout):
            raise ValueError(f"filter shape {w.shape} does not match plan "
                             f"{(*self.kernel, self.cin, self.cout)}")
        ws = split_filters(w, self.stride)
        if scale is not None:
            # n-major channel c = n*Cout + oc: tile the per-oc scale
            # across the prod(s) sub-filter blocks.
            ws = ws * jnp.tile(scale.astype(ws.dtype), self.phases)
        wscale = None
        if self.dtype == "int8":
            from repro.core.quant import quantize_channelwise
            ws, wscale = quantize_channelwise(ws, axis=-1)
        layout = self._bound_layout()
        if layout in ("ocmajor", "wino"):
            ws = to_ocmajor(ws, self.stride)
            if wscale is not None:
                # n-major c = phase*Cout + oc  ->  oc-major oc*N + phase.
                wscale = wscale.reshape(self.phases, self.cout)
                wscale = wscale.T.reshape(-1)
        if layout == "wino":
            # The Winograd filter transform U = G g G^T, folded here so
            # it runs once offline — exactly like the split + BN fold.
            # ws becomes (alpha_h, alpha_w, Cin, Cout*N).
            from repro.kernels.winograd import transform_filters
            ws = transform_filters(ws)
        shards, shard_axis = self.shards, self.shard_axis
        if mesh is not None:
            if axis not in mesh.axis_names:
                raise ValueError(f"mesh has no axis {axis!r}; "
                                 f"axes are {tuple(mesh.axis_names)}")
            shards, shard_axis = int(mesh.shape[axis]), axis
            if shards > 1 and self.cout % shards:
                raise ValueError(
                    f"cout {self.cout} not divisible by mesh axis "
                    f"{axis!r} size {shards}; bind without mesh= to "
                    "replicate this layer")
        if shards > 1 and layout == "nmajor":
            # oc-major/wino channel order is already contiguous per Cout
            # block; n-major needs the shard-blocked permutation so each
            # device's NamedSharding slice is locally n-major.
            ws = to_shardblocked(ws, self.stride, shards, self.phases)
            if wscale is not None:
                wscale = wscale.reshape(self.phases, shards, -1)
                wscale = jnp.swapaxes(wscale, 0, 1).reshape(-1)
        bound = replace(self, ws=ws, bias=bias, layout=layout,
                        wscale=wscale, shards=shards,
                        shard_axis=shard_axis,
                        act=self.act if act is None else act)
        if mesh is not None and not isinstance(ws, jax.core.Tracer):
            bound = bound.shard_put(mesh)
        return bound

    def unbind(self) -> "DeconvPlan":
        return replace(self, ws=None, bias=None, wscale=None,
                       sx_in=None, sx_out=None, chain_out=False,
                       layout="nmajor")

    def with_tile(self, tile: Optional[KernelPlan]) -> "DeconvPlan":
        return replace(self, tile=tile)

    def with_chain(self, sx_in: Optional[Any] = None,
                   sx_out: Optional[Any] = None,
                   chain_out: bool = False) -> "DeconvPlan":
        """Attach static calibrated activation scales (see class doc).

        ``sx_in`` — the input's static scale: execution quantizes the
        f32 input against it with *no* amax reduction, or consumes an
        already-int8 input produced by the previous layer's chained
        epilogue.  ``sx_out`` + ``chain_out=True`` — fold ``1/sx_out``
        into the epilogue and emit int8.  Scales are stored as scalar
        f32 leaves; ``chain_out`` is aux (it keys the jit cache — the
        launch's output dtype is static).  Chained output requires a
        fold-compatible activation: ``relu(y)/s == relu(y/s)`` for
        ``s > 0``, and linear trivially — tanh does not commute with
        the scale, so a tanh layer can head a chain but never emit one.
        """
        if self.dtype != "int8":
            raise ValueError("activation chaining requires an int8 plan")
        if chain_out:
            if sx_out is None:
                raise ValueError("chain_out requires sx_out")
            if self.act not in ("linear", "relu"):
                raise ValueError(
                    f"chain_out cannot fold 1/sx_out through act "
                    f"{self.act!r}; only linear/relu commute with a "
                    "positive scale")
        def _sc(v):
            return None if v is None else jnp.asarray(v, jnp.float32)
        return replace(self, sx_in=_sc(sx_in), sx_out=_sc(sx_out),
                       chain_out=bool(chain_out))


DTYPES = ("native", "int8")


def plan(filter_shape: Sequence[int], stride, padding=0,
         backend: str = "auto", act: str = "linear",
         tile: Optional[KernelPlan] = None,
         output_padding=0, dtype: str = "native") -> DeconvPlan:
    """Compute the split layout for a deconv filter shape.

    ``filter_shape`` is ``(*K, C_in, C_out)`` — its length sets the
    spatial rank: 3 entries = 1-D ``(K, C_in, C_out)``, 4 = 2-D HWIO,
    5 = 3-D DHWIO.  ``padding`` accepts ``int``, a per-dim sequence, or
    per-dim ``(lo, hi)`` pairs exactly like the
    :mod:`repro.core.deconv` implementations, and invalid crops are
    rejected identically; ``output_padding`` (int or per-dim,
    ``0 <= op < s``) grows the high side of the output — the knob that
    makes odd output sizes (25 -> 50 at stride 2) expressible.  The
    result is geometry-only (no filter data): pass it straight to
    :func:`repro.sd.conv_transpose`, or :meth:`DeconvPlan.bind` a
    filter for the presplit execution path.

    ``dtype="int8"`` requests the quantized inference path: ``bind``
    quantizes the scale-folded split filters per output channel and
    ``execute`` runs int8 activations with a dequant epilogue.  Int8
    plans are inference-only — :func:`repro.sd.conv_transpose` rejects
    them (quantization is not usefully differentiable).
    """
    dims = tuple(int(d) for d in filter_shape)
    if len(dims) not in (3, 4, 5):
        raise ValueError(f"filter_shape {filter_shape!r} must have "
                         "3 (1-D), 4 (2-D) or 5 (3-D) entries")
    if dtype not in DTYPES:
        raise ValueError(f"unknown plan dtype {dtype!r}; "
                         f"choose from {DTYPES}")
    rank = len(dims) - 2
    k, (cin, cout) = dims[:rank], dims[rank:]
    st = _ntuple(stride, rank)
    op = _ntuple(output_padding, rank)
    _check_padding(k, padding)
    _check_output_padding(op, st)
    resolved = resolve_backend(backend)
    if resolved == "winograd":
        from repro.kernels.winograd import MAX_TAPS, supported
        kt = sd_geometry(k, st)[0]
        if not supported(kt, dtype):
            raise ValueError(
                f"winograd backend does not support this geometry: "
                f"subfilter taps {kt} (rank {rank}, dtype {dtype!r}); "
                f"requires rank <= 2, 1 <= taps <= {MAX_TAPS}, float "
                f"dtype — use backend='fused' for this layer")
    return DeconvPlan(kernel=k, stride=st,
                      padding=_pads_nd(padding, rank), cin=cin, cout=cout,
                      backend=resolved, act=act, tile=tile,
                      output_padding=op, dtype=dtype)


# ---------------------------------------------------------------------------
# Pytree registration: arrays are leaves, geometry is aux_data.
# ---------------------------------------------------------------------------

def _flatten(p: DeconvPlan):
    # wscale/sx_* are None on float (or unchained) plans; None children
    # are empty subtrees, so float bound plans still flatten to exactly
    # (ws, bias) leaves.
    children = (p.ws, p.bias, p.wscale, p.sx_in, p.sx_out)
    aux = (p.kernel, p.stride, p.padding, p.output_padding, p.cin, p.cout,
           p.backend, p.act, p.layout, p.tile, p.dtype, p.shards,
           p.shard_axis, p.chain_out)
    return children, aux


def _unflatten(aux, children) -> DeconvPlan:
    ws, bias, wscale, sx_in, sx_out = children
    (kernel, stride, padding, output_padding, cin, cout, backend, act,
     layout, tile, dtype, shards, shard_axis, chain_out) = aux
    return DeconvPlan(kernel=kernel, stride=stride, padding=padding,
                      output_padding=output_padding, cin=cin, cout=cout,
                      backend=backend, act=act, layout=layout, tile=tile,
                      dtype=dtype, shards=shards, shard_axis=shard_axis,
                      chain_out=chain_out, ws=ws, bias=bias,
                      wscale=wscale, sx_in=sx_in, sx_out=sx_out)


jax.tree_util.register_pytree_node(DeconvPlan, _flatten, _unflatten)


# ---------------------------------------------------------------------------
# shard_scope: trace-time Cout-shard marking for the stateless form.
# ---------------------------------------------------------------------------

_SHARD_SCOPE = threading.local()


@contextmanager
def shard_scope(shards: int, axis: str = "model"):
    """Trace-time context: while active, model code that builds
    geometry-only plans (e.g. the generative models' traced-params
    path) marks shardable deconv layers ``with_shards(shards, axis)``,
    so ``conv_transpose`` inside ``shard_map`` consumes the local Cout
    slice of ``w`` and all-gathers the output.  Layers whose cout does
    not divide ``shards`` stay replicated — the model decides per
    layer via :func:`current_shard_scope`."""
    prev = getattr(_SHARD_SCOPE, "value", None)
    _SHARD_SCOPE.value = (int(shards), str(axis))
    try:
        yield
    finally:
        _SHARD_SCOPE.value = prev


def current_shard_scope() -> Optional[Tuple[int, str]]:
    """The active ``(shards, axis)`` of :func:`shard_scope`, or None."""
    return getattr(_SHARD_SCOPE, "value", None)
