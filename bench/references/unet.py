"""Plain reference of the pix2pix U-Net generator.

Isola et al., arXiv:1611.07004, appendix 6.1.1, as ``defineG_unet``
(phillipi/pix2pix) and ``UnetGenerator`` (junyanz/pytorch-CycleGAN-and-
pix2pix) compute it, from the layer list of a configuration file:

    e1  = Conv1(x)
    e_i = IN(Conv_i(LReLU_0.2(e_{i-1})))          inner encoder levels
    e_n = Conv_n(LReLU_0.2(e_{n-1}))              innermost, no norm
    u1  = IN(ConvT_1(ReLU(e_n)))
    u_k = IN(ConvT_k(ReLU([e_{n+1-k}, u_{k-1}])))
    y   = tanh(ConvT_n(ReLU([e_1, u_{n-1}])) + b)
    IN(z) = gamma * (z - mean_HW(z)) / sqrt(var_HW(z) + 1e-5) + beta

Each layer of the file says what it does: ``act`` on its (joined) input
(``linear``, ``relu``, ``leaky_relu`` with ``slope``), ``skip`` (the
layer joined ahead of the input, ``[skip, h]`` on channels), ``norm``
(``instance``: per image and channel over height and width, in float32)
and ``bias``.  Every conv and transposed conv is 4x4, stride 2,
padding 1 (PyTorch's), written out here rather than read as "same".
Plain ``jax.numpy`` and ``lax`` in float32, no kernels, plans or
batching; it imports nothing from the program under test.

``precision="highest"`` computes every product in float32 (the TPU's
six-pass mode).  ``precision="high"`` is the control: every product as
three bfloat16 passes (hi*hi + hi*lo + lo*hi), the step below "highest"
on the TPU, written out so that it computes the same on any backend.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

EPS = 1e-5


def init(layers, key):
    """Seeded weights in the program's parameter layout: ``w`` (k, k,
    cin, cout), ``b`` where the layer has a bias, ``gamma`` and ``beta``
    where it has a norm.  None of them is trivial, so the program's bias
    and norm parameters are compared too."""
    params = {}
    for k, layer in zip(jax.random.split(key, len(layers)), layers):
        kw, kb, kg, kt = jax.random.split(k, 4)
        cin, cout, n = layer["cin"], layer["cout"], layer["k"]
        w = jax.random.normal(kw, (n, n, cin, cout), jnp.float32)
        p = {"w": w / math.sqrt(n * n * cin)}
        if layer["bias"]:
            p["b"] = 0.1 * jax.random.normal(kb, (cout,), jnp.float32)
        if layer["norm"] == "instance":
            p["gamma"] = 1.0 + 0.1 * jax.random.normal(kg, (cout,),
                                                       jnp.float32)
            p["beta"] = 0.1 * jax.random.normal(kt, (cout,), jnp.float32)
        params[layer["name"]] = p
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


def _conv(x, w, strides, pad, lhs_dilation, precision):
    def op(a, b, prec, out_type):
        return lax.conv_general_dilated(
            a, b, window_strides=strides, padding=[(pad, pad)] * 2,
            lhs_dilation=lhs_dilation,
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=prec,
            preferred_element_type=out_type)

    return _passes(op, x, w, precision)


def _act(layer, h):
    if layer["act"] == "relu":
        return jnp.maximum(h, 0.0)
    if layer["act"] == "leaky_relu":
        return jnp.where(h >= 0, h, layer["slope"] * h)
    if layer["act"] != "linear":
        raise ValueError(f"unknown act {layer['act']!r}")
    return h


def _instance_norm(h, gamma, beta):
    mean = jnp.mean(h, axis=(1, 2), keepdims=True)
    var = jnp.mean(jnp.square(h - mean), axis=(1, 2), keepdims=True)
    return gamma * (h - mean) / jnp.sqrt(var + EPS) + beta


def forward(config, params, x, precision="highest"):
    """Outputs of the configuration's U-Net for the batch ``x``."""
    h = x.astype(jnp.float32)
    outs = {}
    for layer in config["layers"]:
        p = params[layer["name"]]
        if layer["skip"] is not None:
            h = jnp.concatenate([outs[layer["skip"]], h], axis=-1)
        h = _act(layer, h)
        k, s = layer["k"], layer["s"]
        pad = (k - s) // 2                       # PyTorch's padding=1
        if layer["kind"] == "conv":
            h = _conv(h, p["w"], (s, s), pad, None, precision)
        elif layer["kind"] == "deconv":
            # Transposed conv: dilate the input by the stride and
            # correlate with the spatially flipped filter.
            h = _conv(h, p["w"][::-1, ::-1], (1, 1), k - 1 - pad, (s, s),
                      precision)
        else:
            raise ValueError(f"unknown layer kind {layer['kind']!r}")
        if layer["bias"]:
            h = h + p["b"]
        if layer["norm"] == "instance":
            h = _instance_norm(h, p["gamma"], p["beta"])
        outs[layer["name"]] = h
    return jnp.tanh(h) if config["final_tanh"] else h
