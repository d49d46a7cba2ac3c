"""Plain reference of a sequential generator or encoder-decoder.

The layer list of a configuration file (fc, conv and transposed conv
with TF "same" padding, folded batch norm as a per-channel scale and
bias, ReLU between layers, optional final tanh), written in plain
``jax.numpy`` and ``lax`` in float32, with no kernels, plans or batching.
It imports nothing from the program under test.

``precision="highest"`` computes every product in float32 (the TPU's
six-pass mode).  ``precision="high"`` is the control: every product as
three bfloat16 passes (hi*hi + hi*lo + lo*hi), the step below "highest"
on the TPU, written out so that it computes the same on any backend.
The split uses ``reduce_precision``, which XLA keeps, where a round trip
through a bfloat16 convert may be folded away.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax


def init(layers, key):
    """Seeded weights in the program's parameter layout: fc ``w`` (cin,
    cout) and ``b``; conv and deconv ``w`` (*k, cin, cout), ``b`` and the
    folded batch-norm ``scale``.  Biases and scales are not trivial, so
    the program's bias and scale folding is compared too."""
    params = {}
    for k, layer in zip(jax.random.split(key, len(layers)), layers):
        kw, kb, ks = jax.random.split(k, 3)
        cin, cout = layer["cin"], layer["cout"]
        b = 0.1 * jax.random.normal(kb, (cout,), jnp.float32)
        if layer["kind"] == "fc":
            w = jax.random.normal(kw, (cin, cout), jnp.float32)
            params[layer["name"]] = {"w": w / math.sqrt(cin), "b": b}
            continue
        rank = len(layer["in_hw"])
        w = jax.random.normal(kw, (layer["k"],) * rank + (cin, cout),
                              jnp.float32)
        params[layer["name"]] = {
            "w": w / math.sqrt(layer["k"] ** rank * cin), "b": b,
            "scale": 1.0 + 0.1 * jax.random.normal(ks, (cout,), jnp.float32)}
    return params


def _passes(op, a, b, precision):
    if precision == "highest":
        return op(a, b, lax.Precision.HIGHEST, None)
    if precision != "high":
        raise ValueError(f"unknown precision {precision!r}")

    def split(x):
        hi = lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
        lo = lax.reduce_precision(x - hi, exponent_bits=8, mantissa_bits=7)
        return hi.astype(jnp.bfloat16), lo.astype(jnp.bfloat16)

    (ah, al), (bh, bl) = split(a), split(b)
    f32 = jnp.float32
    return (op(al, bh, None, f32) + op(ah, bl, None, f32)) + op(ah, bh, None,
                                                                   f32)


def _dimension_numbers(rank):
    space = "DHW"[3 - rank:]
    return (f"N{space}C", f"{space}IO", f"N{space}C")


def _conv(x, w, strides, padding, lhs_dilation, precision):
    rank = x.ndim - 2

    def op(a, b, prec, out_type):
        return lax.conv_general_dilated(
            a, b, window_strides=strides, padding=padding,
            lhs_dilation=lhs_dilation,
            dimension_numbers=_dimension_numbers(rank), precision=prec,
            preferred_element_type=out_type)

    return _passes(op, x, w, precision)


def _matmul(a, b, precision):
    def op(x, y, prec, out_type):
        return jnp.matmul(x, y, precision=prec,
                          preferred_element_type=out_type)

    return _passes(op, a, b, precision)


def layer(config, i, params, h, precision="highest"):
    """Layer ``i`` of the configuration before its activation, on ``h``
    in whatever float type it comes (float64 for a witness)."""
    layers = config["layers"]
    spec = layers[i]
    p = params[spec["name"]]
    if spec["kind"] == "fc":
        h = _matmul(h.reshape(h.shape[0], -1), p["w"], precision) + p["b"]
        nxt = layers[i + 1] if i + 1 < len(layers) else None
        if nxt is not None and nxt["kind"] != "fc":
            h = h.reshape(h.shape[0], *nxt["in_hw"], nxt["cin"])
        return h
    rank = len(spec["in_hw"])
    if spec["kind"] == "conv":
        h = _conv(h, p["w"], (spec["s"],) * rank, "SAME", None, precision)
    elif spec["kind"] == "deconv":
        # Transposed conv: dilate the input by the stride and correlate
        # with the spatially flipped filter; TF "same" crops max(k-s, 0)
        # outputs, the smaller half in front.
        k, s = spec["k"], spec["s"]
        crop = max(k - s, 0)
        pad = [(k - 1 - crop // 2, k - 1 - (crop - crop // 2))] * rank
        flip = p["w"][(slice(None, None, -1),) * rank]
        h = _conv(h, flip, (1,) * rank, pad, (s,) * rank, precision)
    else:
        raise ValueError(f"unknown layer kind {spec['kind']!r}")
    return h * p["scale"] + p["b"]


def activate(config, i, h):
    """ReLU between layers; the optional tanh after the last."""
    if i < len(config["layers"]) - 1:
        return jax.nn.relu(h)
    return jnp.tanh(h) if config["final_tanh"] else h


def forward(config, params, x, precision="highest"):
    """Outputs of the configuration's network for the batch ``x``."""
    h = x.astype(jnp.float32)
    for i in range(len(config["layers"])):
        h = activate(config, i, layer(config, i, params, h, precision))
    return h
