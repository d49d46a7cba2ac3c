"""Stateless split-deconvolution entry points.

Two runtime forms over the same :class:`~repro.sd.plan.DeconvPlan`:

* :func:`conv_transpose` — the training/authoring form.  Takes the
  *original* HWIO deconv filter, splits it in-trace (a pure layout op),
  runs the plan's backend, and is differentiable through a
  ``jax.custom_vjp`` whose backward is standard convolutions over the
  split layout (:mod:`repro.sd.grad`).  Because the backward never
  differentiates the forward, the fused Pallas kernel is trainable too
  — and for ``backend="fused"`` plans of rank 1/2 the backward's two
  convolutions themselves run on the Pallas kernels.
* :func:`execute` — the deployment form.  Takes a *bound* plan (filters
  pre-split exactly once via ``plan.bind``), runs bias + activation in
  the epilogue, and never touches ``split_filters``.  Bound plans are
  pytrees, so this composes with ``jit``/``shard_map`` with the plan
  passed as an ordinary argument.

Both forms compute exactly the transposed convolution of
``repro.core.deconv.native_deconv`` (plus the optional epilogue).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.deconv import sd_deconv_presplit, split_filters
from . import grad as _grad
from .plan import DeconvPlan, to_ocmajor


def _gather_cout(plan: DeconvPlan, y: jax.Array) -> jax.Array:
    """Epilogue collective of a Cout-sharded plan: one tiled all-gather
    re-assembles the channel axis from each device's Cout block.  The
    all-gather (vs a reduce-scatter) is the right collective here: every
    next-layer filter slice needs the *full* Cin, so the inter-layer
    tensor must be whole on every model-axis device anyway, and the
    shard-blocked channel order makes the tiled concatenation land each
    block exactly where the unsharded layout would have it."""
    try:
        return jax.lax.all_gather(y, plan.shard_axis, axis=y.ndim - 1,
                                  tiled=True)
    except NameError as e:
        raise ValueError(
            f"plan is Cout-sharded {plan.shards} ways over mesh axis "
            f"{plan.shard_axis!r}, which is not bound here — run it "
            "under shard_map on a mesh with that axis (see "
            "sd.execute_spmd), or rebind without mesh=") from e


def _run_presplit(plan: DeconvPlan, x: jax.Array, ws: jax.Array,
                  layout: str, bias: Optional[jax.Array],
                  act: str) -> jax.Array:
    """Dispatch pre-split filters to the plan's execution backend,
    any rank: the fused Pallas kernel for ranks 1-2 (1-D lowers as H=1
    2-D; the P_K/user crop lives inside the kernel, so the output is
    written once), the depth-folded
    Pallas + grouped-XLA interleave for rank 3, and the grouped-XLA
    conv + pixel-shuffle for the xla backend.  The winograd backend
    runs the F(2,r) fast-algorithm Pallas kernel: a bound plan
    (layout "wino") carries the G g G^T-transformed filters from
    ``plan.bind``; the in-trace (conv_transpose) form transforms the
    freshly split filters here — pure layout + matmul ops, so the
    custom_vjp backward is untouched."""
    if plan.backend == "winograd":
        from repro.kernels import ops                 # lazy: pulls Pallas
        from repro.kernels.winograd import transform_filters
        if layout != "wino":
            u = transform_filters(to_ocmajor(ws, plan.stride))
        else:
            u = ws
        if plan.rank == 1:
            return ops.sd_deconv_presplit_wino_1d(
                x, u, plan.kernel, plan.stride, plan.padding,
                output_padding=plan.output_padding, bias=bias, act=act,
                plan=plan.tile)
        return ops.sd_deconv_presplit_wino(
            x, u, plan.kernel, plan.stride, plan.padding,
            output_padding=plan.output_padding, bias=bias, act=act,
            plan=plan.tile)
    if plan.backend == "fused":
        from repro.kernels import ops                 # lazy: pulls Pallas
        if plan.rank == 3:
            # depth-into-batch Pallas convs + grouped-XLA interleave;
            # consumes n-major filters like the XLA path.
            ws_n = ws if layout == "nmajor" else None
            assert ws_n is not None, "3-D fused lowering is n-major"
            return ops.sd_deconv_presplit_fused_3d(
                x, ws_n, plan.kernel, plan.stride, plan.padding,
                output_padding=plan.output_padding, bias=bias, act=act,
                plan=plan.tile)
        ws_oc = ws if layout == "ocmajor" else to_ocmajor(ws, plan.stride)
        if plan.rank == 1:
            return ops.sd_deconv_presplit_fused_1d(
                x, ws_oc, plan.kernel, plan.stride, plan.padding,
                output_padding=plan.output_padding, bias=bias, act=act,
                plan=plan.tile)
        return ops.sd_deconv_presplit_fused(
            x, ws_oc, plan.kernel, plan.stride, plan.padding,
            output_padding=plan.output_padding,
            bias=bias, act=act, plan=plan.tile)
    ws_n = ws if layout == "nmajor" else None
    assert ws_n is not None, "xla backend consumes n-major filters"
    y = sd_deconv_presplit(x, ws_n.astype(x.dtype), plan.kernel,
                           plan.stride, plan.padding,
                           output_padding=plan.output_padding)
    if bias is not None:
        y = y + bias.astype(y.dtype)
    if act == "relu":
        y = jax.nn.relu(y)
    elif act == "tanh":
        y = jnp.tanh(y)
    return y


def _run_presplit_int8(plan: DeconvPlan, x: jax.Array) -> jax.Array:
    """Quantized deployment path of a bound int8 plan.

    Without calibration (``plan.sx_in is None``) activations are
    quantized *dynamically, per sample* (the zero rows a bucketed
    server pads a batch with can never perturb real samples), the
    stride-1 conv runs int8 x int8 -> int32, and the combined dequant
    scale — per-sample activation scale times the plan's per-channel
    filter scale (BN already folded in) — is applied before the
    interleave, where each phase channel still has its own scale.
    Output is f32.

    A *calibrated* plan (``sx_in`` set) replaces the per-sample amax
    pass with the static scale: an f32 input quantizes elementwise
    against ``sx_in`` (saturating clamp, no reduction anywhere on the
    path), and an int8 input — the previous layer's chained epilogue
    output — is consumed directly.  With ``chain_out`` the epilogue
    additionally folds ``1/sx_out`` into the combined scale *and* the
    bias (``act(y)/s == act(y/s)`` for linear/relu, ``s > 0``) and
    re-quantizes the activated tile to int8 in VMEM, so the
    inter-layer tensor lives in HBM as int8.

    The fused backend does all of this inside the fused Pallas
    kernel (int32 VMEM accumulator, scale staged once per tile — one
    static row for calibrated plans).  The xla backend keeps the same
    quantization numerics but computes the conv on f32-cast operands —
    XLA's CPU int8 conv path is orders of magnitude slower than its
    f32 conv, so off-TPU the honest-int8 wall-clock would be nonsense;
    numerically the two differ only by f32-vs-int32 accumulation order.
    """
    from repro.core.quant import quantize_act, quantize_static
    wscale = plan.wscale.astype(jnp.float32)
    if plan.sx_in is not None:
        sx = plan.sx_in.astype(jnp.float32)
        xq = x if x.dtype == jnp.int8 else quantize_static(x, sx)
        comb = (sx * wscale)[None, :]              # (1, NC): one static row
    else:
        if x.dtype == jnp.int8:
            raise ValueError("int8 input requires a calibrated plan "
                             "(sx_in) — the dynamic path has no scale "
                             "for it")
        xq, sx = quantize_act(x)
        comb = sx[:, None] * wscale[None, :]
    bias, act = plan.bias, plan.act
    out_dtype = None
    if plan.chain_out:
        sn = plan.sx_out.astype(jnp.float32)
        comb = comb / sn
        if bias is not None:
            bias = bias.astype(jnp.float32) / sn
        out_dtype = "int8"
    if plan.backend == "fused":
        from repro.kernels import ops
        if plan.rank == 3:
            assert plan.layout == "nmajor"
            return ops.sd_deconv_presplit_fused_3d(
                xq, plan.ws, plan.kernel, plan.stride, plan.padding,
                output_padding=plan.output_padding, bias=bias, act=act,
                scale=comb, out_dtype=out_dtype, plan=plan.tile)
        assert plan.layout == "ocmajor"
        fn = (ops.sd_deconv_presplit_fused_1d if plan.rank == 1
              else ops.sd_deconv_presplit_fused)
        return fn(xq, plan.ws, plan.kernel, plan.stride, plan.padding,
                  output_padding=plan.output_padding, bias=bias, act=act,
                  scale=comb, out_dtype=out_dtype, plan=plan.tile)
    assert plan.layout == "nmajor"
    rank = plan.rank
    space1 = (1,) * rank

    def conv_fn(xp, wsq):
        from jax import lax
        from repro.core.deconv import conv_dimension_numbers
        y = lax.conv_general_dilated(
            xp.astype(jnp.float32), wsq.astype(jnp.float32),
            window_strides=(1,) * rank, padding="VALID",
            dimension_numbers=conv_dimension_numbers(rank))
        # dequant per (sample, n-major channel) BEFORE depth_to_space;
        # a static (1, NC) comb broadcasts over the batch.
        return y * comb.reshape(comb.shape[0], *space1, comb.shape[1])

    y = sd_deconv_presplit(xq, plan.ws, plan.kernel, plan.stride,
                           plan.padding, conv_fn=conv_fn,
                           output_padding=plan.output_padding)
    if bias is not None:
        y = y + bias.astype(y.dtype)
    if act == "relu":
        y = jax.nn.relu(y)
    elif act == "tanh":
        y = jnp.tanh(y)
    if out_dtype is not None:
        # Chained epilogue: same round + saturating clamp as the kernel.
        y = jnp.clip(jnp.round(y), -127.0, 127.0).astype(jnp.int8)
    return y


# ---------------------------------------------------------------------------
# conv_transpose: pure, differentiable, jit/vmap/shard_map-composable.
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def conv_transpose(plan: DeconvPlan, x: jax.Array, w: jax.Array,
                   b: Optional[jax.Array] = None) -> jax.Array:
    """Transposed convolution of ``x`` with ``w`` via the split layout.

    ``plan`` must be geometry-only (unbound) — it carries no arrays, so
    it is a static pytree that crosses ``jit`` boundaries as an
    argument and hashes into the compile cache.  ``w`` is the plain
    HWIO deconv filter; ``b`` an optional per-output-channel bias.
    Differentiable in ``x``, ``w`` and ``b`` (see :mod:`repro.sd.grad`);
    no epilogue activation is applied (compose it outside, where it is
    differentiable for free).

    A ``plan.with_shards(n, axis)`` plan is the SPMD training form:
    under ``shard_map``, ``w`` is each device's ``cout/n`` slice of the
    filter, the split conv runs on that slice only, and the output's
    channel axis is all-gathered over ``axis`` — so the result (and the
    cotangent flowing back in) is the full-channel tensor on every
    device, while the ``custom_vjp`` backward keeps the filter grad
    local to the shard and ``psum``\\ s only the input grad.
    """
    return _fwd_value(plan, x, w, b)


def _fwd_value(plan, x, w, b):
    if plan.bound:
        raise ValueError(
            "conv_transpose takes a geometry-only plan plus the raw "
            "filter; use repro.sd.execute(plan, x) for bound plans")
    if plan.dtype == "int8":
        raise ValueError(
            "int8 plans are inference-only: quantization is not "
            "usefully differentiable — bind() the plan and use "
            "repro.sd.execute, or build a dtype='native' plan to train")
    ws = split_filters(w, plan.stride)
    y = _run_presplit(plan, x, ws, "nmajor", None, "linear")
    if plan.shards > 1:
        y = _gather_cout(plan, y)
    return y if b is None else y + b.astype(y.dtype)


def _fwd(plan, x, w, b):
    return _fwd_value(plan, x, w, b), (x, w, b)


def _bwd(plan, res, dy):
    x, w, b = res
    dx, dw = _grad.conv_transpose_vjp(plan, x, w, dy)
    # f32 accumulation for the bias reduction (bf16 partial sums drift);
    # cast to the bias primal's dtype like dx/dw — an f32 bias under
    # bf16 activations must get an f32 cotangent back.  Reduce over the
    # batch + every spatial axis (rank-generic).
    db = (jnp.sum(dy.astype(jnp.float32),
                  axis=tuple(range(dy.ndim - 1))).astype(b.dtype)
          if b is not None else None)
    return dx, dw, db


conv_transpose.defvjp(_fwd, _bwd)


def split_weights(plan: DeconvPlan, w: jax.Array) -> jax.Array:
    """The offline filter transform for ``plan`` (n-major layout).
    Differentiable (pure pad + permutation)."""
    return split_filters(w, plan.stride)


# ---------------------------------------------------------------------------
# execute: the presplit-once deployment path.
# ---------------------------------------------------------------------------

def execute(plan: DeconvPlan, x: jax.Array) -> jax.Array:
    """Run a *bound* plan: pre-split (scale-folded) filters, bias and
    activation epilogue.  The hot path of :class:`repro.engine.SDEngine`
    — no splitting, no BN arithmetic, no plan search here."""
    if not plan.bound:
        raise ValueError("execute() needs a bound plan; call "
                         "plan.bind(w, scale, bias) once offline, or use "
                         "conv_transpose(plan, x, w) for the stateless form")
    if plan.dtype == "int8":
        y = _run_presplit_int8(plan, x)
    else:
        y = _run_presplit(plan, x, plan.ws, plan.layout, plan.bias,
                          plan.act)
    # Cout-sharded plan: bias + act above are per-local-channel, so the
    # whole epilogue ran on the shard; one collective closes the layer.
    if plan.shards > 1:
        y = _gather_cout(plan, y)
    return y


def execute_spmd(plan: DeconvPlan, x: jax.Array, mesh,
                 dp_axis: str = "data") -> jax.Array:
    """Run a bound plan on a device mesh under ``shard_map``: batch
    split over ``dp_axis`` (when it divides), Cout split per the plan's
    own ``shards``/``shard_axis``.  This is the standalone entry point
    — serving composes the same specs into its per-net executable
    (see ``launch.serve_gen``); unsharded plans on a model axis simply
    run replicated.  Output matches single-device :func:`execute`."""
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P
    dp = int(mesh.shape[dp_axis]) if dp_axis in mesh.axis_names else 1
    batch_ax = dp_axis if (dp > 1 and x.shape[0] % dp == 0) else None
    xspec = P(*((batch_ax,) + (None,) * (x.ndim - 1)))
    f = shard_map(lambda p, xx: execute(p, xx), mesh=mesh,
                  in_specs=(plan.shard_specs(), xspec),
                  out_specs=xspec, check_rep=False)
    return f(plan, x)
