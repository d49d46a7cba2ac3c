"""Ahead-of-time compiles of the SD Pallas kernels for a described TPU v5e.

Interpret mode accepts block shapes, layouts and reshapes that Mosaic
refuses, so the CPU parity suite alone cannot tell whether the kernels
lower for the chip.  These tests compile the raw kernels with
``interpret=False`` against a described ``v5e:2x2`` topology (the TPU
compiler is installed even where no chip is attached) at the main
path's real widths and heuristic tiles, and check that the compiled
program holds the Pallas kernel (``tpu_custom_call``).  Nothing runs:
numerics are the parity suite's job.

``kernels.ops`` picks interpret mode from the host's backend, which is
the CPU here, so the tests call the raw kernels directly.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.accounting import dcgan, mde, pix2pix
from repro.core.deconv import (_pads, deconv_output_shape,
                               same_deconv_pads, sd_geometry)
from repro.kernels import autotune, sd_conv, winograd
from repro.kernels.autotune import ConvGeom

_DCGAN = {l.name: l for l in dcgan().deconv_layers()}
_MDE = {l.name: l for l in mde().deconv_layers()}
_UNET = [l for l in pix2pix().deconv_layers()]
# (layer, batch): the DCGAN serving buckets' extremes, MDE's widest
# decoder layer, whose 512-wide output takes the width-tiling path, and
# every pix2pix U-Net deconv at the cell's bucket 32 (1x1 and 2x2
# inputs, Cin up to 1024).
LAYERS = {"dcgan_d1": (_DCGAN["d1"], 16), "dcgan_d2": (_DCGAN["d2"], 16),
          "dcgan_d3": (_DCGAN["d3"], 16), "mde_up1": (_MDE["up1"], 1),
          **{f"pix2pix_{l.name}": (l, 32) for l in _UNET}}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it.
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _launch(name, dtype="", qout=False):
    """The fused launch the engine makes for one paper layer: operand
    shapes, the heuristic tile and the pad/crop/out_space arguments."""
    layer, b = LAYERS[name]
    k, s = layer.k, layer.s
    pads = same_deconv_pads(k, s)
    (kt, _), pk, (pi, _) = sd_geometry((k, k), (s, s))
    out_space = deconv_output_shape(layer.in_hw, (k, k), (s, s), pads, 0)
    crop = tuple(p + lo for p, (lo, _) in zip(pk, _pads(pads)))
    geom = dataclasses.replace(
        ConvGeom.from_deconv(b, *layer.in_hw, layer.cin, layer.cout, k, s,
                             padding=pads, dtype=dtype), qout=qout)
    tile = autotune.heuristic_plan(geom)
    kwargs = dict(th=tile.th, tw=tile.tw, tcin=tile.tcin, tcout=tile.tcout,
                  pad=((pi, pi), (pi, pi)), crop=crop,
                  out_space=tuple(out_space))
    return layer, b, kt, s, kwargs


@pytest.mark.parametrize("name,dtype", [
    ("dcgan_d1", jnp.float32), ("dcgan_d2", jnp.float32),
    ("dcgan_d3", jnp.float32), ("mde_up1", jnp.float32),
    ("dcgan_d2", jnp.bfloat16)])
def test_fused_float_compiles(one_chip, name, dtype):
    layer, b, kt, s, kw = _launch(name)
    x = jax.ShapeDtypeStruct((b, *layer.in_hw, layer.cin), dtype,
                             sharding=one_chip)
    ws = jax.ShapeDtypeStruct((kt, kt, layer.cin, layer.cout * s * s),
                              dtype, sharding=one_chip)
    bias = jax.ShapeDtypeStruct((layer.cout,), jnp.float32,
                                sharding=one_chip)
    text = _compile_text(
        lambda x, ws, bias: sd_conv.sd_fused_pallas(
            x, ws, s, bias=bias, act="relu", interpret=False, **kw),
        x, ws, bias)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("name", [f"pix2pix_{l.name}" for l in _UNET])
def test_unet_deconvs_compile(one_chip, name):
    """The U-Net's launches as the engine makes them: a linear epilogue
    (instance norm follows, or tanh after u8) and a bias on u8 alone."""
    layer, b, kt, s, kw = _launch(name)
    x = jax.ShapeDtypeStruct((b, *layer.in_hw, layer.cin), jnp.float32,
                             sharding=one_chip)
    ws = jax.ShapeDtypeStruct((kt, kt, layer.cin, layer.cout * s * s),
                              jnp.float32, sharding=one_chip)
    args = [x, ws]
    if layer.bias:
        args.append(jax.ShapeDtypeStruct((layer.cout,), jnp.float32,
                                         sharding=one_chip))
    text = _compile_text(
        lambda x, ws, *bias: sd_conv.sd_fused_pallas(
            x, ws, s, bias=bias[0] if bias else None, act="linear",
            interpret=False, **kw),
        *args)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("chained", [False, True], ids=["dynamic", "chained"])
def test_fused_int8_compiles(one_chip, chained):
    layer, b, kt, s, kw = _launch("dcgan_d2", dtype="int8", qout=chained)
    x = jax.ShapeDtypeStruct((b, *layer.in_hw, layer.cin), jnp.int8,
                             sharding=one_chip)
    ws = jax.ShapeDtypeStruct((kt, kt, layer.cin, layer.cout * s * s),
                              jnp.int8, sharding=one_chip)
    scale = jax.ShapeDtypeStruct((1 if chained else b, layer.cout * s * s),
                                 jnp.float32, sharding=one_chip)
    text = _compile_text(
        lambda x, ws, scale: sd_conv.sd_fused_pallas(
            x, ws, s, scale=scale, act="relu",
            out_dtype="int8" if chained else None, interpret=False, **kw),
        x, ws, scale)
    assert "tpu_custom_call" in text


def test_conv_kernel_compiles(one_chip):
    """The generic conv kernel as the backward's input-grad launch runs
    it: a FULL conv of dy1 (pad K_T - 1) cropped to the input."""
    layer, b = LAYERS["dcgan_d3"]
    kt, s = 3, layer.s
    h, w = layer.in_hw
    o1 = (h + 2 * (kt - 1) - kt + 1, w + 2 * (kt - 1) - kt + 1)
    geom = ConvGeom(b, o1[0] + 2 * (kt - 1), o1[1] + 2 * (kt - 1),
                    layer.cout * s * s, layer.cin, kt, 1, tag="dx")
    tile = autotune.heuristic_plan(geom)
    dy1 = jax.ShapeDtypeStruct((b, *o1, layer.cout * s * s), jnp.float32,
                               sharding=one_chip)
    wt = jax.ShapeDtypeStruct((kt, kt, layer.cout * s * s, layer.cin),
                              jnp.float32, sharding=one_chip)
    text = _compile_text(
        lambda dy1, wt: sd_conv.sd_conv_pallas(
            dy1, wt, th=tile.th, tw=tile.tw, tcin=tile.tcin,
            tcout=tile.tcout, pad=((kt - 1, kt - 1), (kt - 1, kt - 1)),
            out_start=(kt - 1, kt - 1), out_size=(h, w), interpret=False),
        dy1, wt)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("name", ["dcgan_d1", "dcgan_d3"])
def test_filter_grad_compiles(one_chip, name):
    from repro.kernels.ops import _dw_tiles
    layer, b = LAYERS[name]
    s = layer.s
    kt, pi = 3, 2
    h, w = layer.in_hw
    o1 = (h + 2 * pi - kt + 1, w + 2 * pi - kt + 1)
    nco = layer.cout * s * s
    tr, tcin, tcout = _dw_tiles(o1, w + 2 * pi, (kt, kt), layer.cin, nco)
    x = jax.ShapeDtypeStruct((b, h, w, layer.cin), jnp.float32,
                             sharding=one_chip)
    dy1 = jax.ShapeDtypeStruct((b, *o1, nco), jnp.float32,
                               sharding=one_chip)
    text = _compile_text(
        lambda x, dy1: sd_conv.sd_filter_grad_pallas(
            x, dy1, (kt, kt), pad=((pi, pi), (pi, pi)), tr=tr, tcin=tcin,
            tcout=tcout, interpret=False),
        x, dy1)
    assert "tpu_custom_call" in text


def test_winograd_refused_on_tpu(one_chip):
    """The Winograd kernel does not lower with Mosaic: on TPU it raises
    the compiler's reason instead of falling back to interpret mode."""
    layer, b, kt, s, kw = _launch("dcgan_d2")
    alpha = winograd.output_tile(kt) + kt - 1
    x = jax.ShapeDtypeStruct((b, *layer.in_hw, layer.cin), jnp.float32,
                             sharding=one_chip)
    u = jax.ShapeDtypeStruct((alpha, alpha, layer.cin, layer.cout * s * s),
                             jnp.float32, sharding=one_chip)
    with pytest.raises(Exception, match="gather"):
        _compile_text(
            lambda x, u: winograd.sd_wino_pallas(
                x, u, (kt, kt), s, interpret=False, **kw),
            x, u)
