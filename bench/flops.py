"""Operations and bytes of a configuration, from its layer shapes alone.

The arithmetic follows arXiv:1907.01773 (the split-deconvolution paper):

* useful MACs: fc ``cin*cout``; conv ``prod(out_hw) * k^d * cin * cout``;
  deconv ``prod(in_hw) * k^d * cin * cout`` (every real input pixel meets
  every filter tap once);
* executed split-deconv MACs: useful MACs times ``(s*ceil(k/s)/k)^d``,
  the slots the zero-expanded split filters add (1 for ``s == 1``);
* bytes of one split-deconv launch: its input, its split filters and its
  output, each read or written once.

It is kept here, apart from the program's own accounting, so that the
yardstick does not move when the program changes.
"""

from __future__ import annotations

import math
from typing import Dict, List, Sequence

ITEMSIZE = {"float32": 4, "bfloat16": 2, "int8": 1}
# Which published peak a dtype's matmuls are measured against.  v5e
# publishes no float32 peak; float32 at "highest" runs as several bf16
# passes, so its share of the bf16 peak cannot come near 100%.
PEAK_KEY = {"float32": "bf16_flops_per_s", "bfloat16": "bf16_flops_per_s",
            "int8": "int8_ops_per_s"}


def out_hw(layer: dict) -> List[int]:
    if layer["kind"] == "fc":
        return []
    if layer.get("padding", "same") != "same":
        raise ValueError(f"layer {layer['name']}: only 'same' padding")
    s = layer["s"]
    if layer["kind"] == "conv":
        return [-(-n // s) for n in layer["in_hw"]]
    return [n * s for n in layer["in_hw"]]


def useful_macs(layer: dict) -> int:
    if layer["kind"] == "fc":
        return layer["cin"] * layer["cout"]
    taps = layer["k"] ** len(layer["in_hw"]) * layer["cin"] * layer["cout"]
    if layer["kind"] == "conv":
        return math.prod(out_hw(layer)) * taps
    return math.prod(layer["in_hw"]) * taps


def sd_expansion(layer: dict) -> float:
    if layer["kind"] != "deconv" or layer["s"] == 1:
        return 1.0
    k, s = layer["k"], layer["s"]
    return (s * -(-k // s) / k) ** len(layer["in_hw"])


def sd_macs(layer: dict) -> int:
    """MACs the split-deconv kernel executes for one image."""
    return int(round(useful_macs(layer) * sd_expansion(layer)))


def sd_bytes(layer: dict, batch: int, itemsize: int) -> int:
    """HBM bytes of one split-deconv launch of ``batch`` images."""
    n_in = batch * math.prod(layer["in_hw"]) * layer["cin"]
    n_w = int(round(layer["k"] ** len(layer["in_hw"]) * layer["cin"]
                    * layer["cout"] * sd_expansion(layer)))
    n_out = batch * math.prod(out_hw(layer)) * layer["cout"]
    return (n_in + n_w + n_out) * itemsize


def model_flops(layers: Sequence[dict]) -> int:
    """Useful FLOPs of one image (2 per MAC), without the SD expansion."""
    return 2 * sum(useful_macs(l) for l in layers)


def sd_kernel_launches(layers: Sequence[dict], batch: int,
                       itemsize: int) -> List[Dict[str, float]]:
    """FLOPs and bytes of each split-deconv kernel call in one launch."""
    return [{"layer": l["name"], "flops": 2 * sd_macs(l) * batch,
             "bytes": sd_bytes(l, batch, itemsize)}
            for l in layers if l["kind"] == "deconv"]


def least_seconds(calls: Sequence[Dict[str, float]],
                  peak_flops: float, bytes_per_s: float):
    """Least time the chip could take for ``calls``: per call, the larger
    of its FLOPs over the peak and its bytes over the bandwidth.  Returns
    (seconds, bound), where bound names the side that gives more of it."""
    compute = memory = 0.0
    for c in calls:
        tc, tm = c["flops"] / peak_flops, c["bytes"] / bytes_per_s
        if tc >= tm:
            compute += tc
        else:
            memory += tm
    return compute + memory, ("compute" if compute >= memory else "memory")
