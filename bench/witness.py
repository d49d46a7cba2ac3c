#!/usr/bin/env python3
"""Where a gap between the chip's float32 and the host's lies (run on the
chip).

    python3 bench/witness.py --workload dcgan.offline --seeds 1,2 \
        --batch 8 --out witness.json

The plain reference is computed four ways on the same weights and inputs:
on the chip at "highest" (the yardstick the benchmark compares with), on
the chip at "high" (the control), on the host CPU in float32, and on the
host CPU in float64, the arbiter.  For each layer, every way is fed the
float64 chain's input rounded to float32, so the layer's gap is its own;
its activation (ReLU, the final tanh) is read apart, on the float64
pre-activation rounded to float32.  The whole network is read too.  Each
gap is ``l2_rel_err``: the norm of the difference over the norm of the
float64 result, by the worst image.
"""

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402
from calibrate import seed_list  # noqa: E402
from traffic import input_pool  # noqa: E402


def l2_gap(out, ref) -> float:
    d = (np.asarray(out, np.float64) - np.asarray(ref, np.float64))
    d, r = d.reshape(len(d), -1), np.asarray(ref, np.float64)
    r = r.reshape(len(r), -1)
    return float(np.max(np.linalg.norm(d, axis=1) / np.linalg.norm(r, axis=1)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seed_list, required=True)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    import jax
    cell = harness.Cell.load(args.workload)
    harness.enable_compile_cache()
    chip = harness.find_chips(cell.chips)[0]
    cpu = jax.devices("cpu")[0]
    config = next(iter(cell.configs.values()))
    ref = harness.load_module(os.path.join(
        harness.BENCH, "references", config["reference"] + ".py"))
    n = len(config["layers"])
    ways = {"chip_highest": (chip, "highest"), "chip_high": (chip, "high"),
            "cpu_f32": (cpu, "highest")}
    fns = {}

    def run(kind, i, way, params, h):
        key = (kind, i, way)
        if key not in fns:
            prec = ways[way][1]
            if kind == "layer":
                fns[key] = jax.jit(lambda p, x: ref.layer(config, i, p, x,
                                                          prec))
            elif kind == "act":
                fns[key] = jax.jit(lambda x: ref.activate(config, i, x))
            else:
                fns[key] = jax.jit(lambda p, x: ref.forward(config, p, x,
                                                            prec))
        dev = ways[way][0]
        x = jax.device_put(np.asarray(h, np.float32), dev)
        if kind == "act":
            return np.asarray(fns[key](x))
        return np.asarray(fns[key](jax.device_put(params, dev), x))

    rows = []
    for seed in args.seeds:
        state = np.random.SeedSequence([seed, 0x3E1]).generate_state(2)
        with jax.default_device(cpu):
            key = jax.random.wrap_key_data(state.astype(np.uint32))
            params = ref.init(config["layers"], key)
        x = input_pool(harness.input_shape(config), config["input"]["dist"],
                       args.batch, seed)
        with jax.enable_x64(True), jax.default_device(cpu):
            p64 = jax.tree_util.tree_map(
                lambda a: jax.numpy.asarray(a, jax.numpy.float64), params)
            h64 = jax.numpy.asarray(x, jax.numpy.float64)
        layers = []
        for i, spec in enumerate(config["layers"]):
            h32 = np.asarray(h64, np.float32)
            with jax.enable_x64(True), jax.default_device(cpu):
                pre64 = np.asarray(ref.layer(config, i, p64,
                                             jax.numpy.asarray(h32, np.float64)))
                pre_chain = ref.layer(config, i, p64, h64)
                pre_r = np.asarray(pre64, np.float32)
                act64 = np.asarray(ref.activate(
                    config, i, jax.numpy.asarray(pre_r, np.float64)))
                h64 = ref.activate(config, i, pre_chain)
            row = {"layer": spec["name"], "kind": spec["kind"]}
            for way in ways:
                row[way] = l2_gap(run("layer", i, way, params, h32), pre64)
            act = ("tanh" if i == n - 1 and config["final_tanh"] else
                   "relu" if i < n - 1 else None)
            if act:
                row[act + "_chip"] = l2_gap(
                    run("act", i, "chip_highest", None, pre_r), act64)
                row[act + "_cpu"] = l2_gap(
                    run("act", i, "cpu_f32", None, pre_r), act64)
            layers.append(row)
        whole = {way: run("forward", -1, way, params, x) for way in ways}
        out64 = np.asarray(h64)
        net = {way + "_vs_f64": l2_gap(out, out64)
               for way, out in whole.items()}
        net["chip_highest_vs_cpu_f32"] = l2_gap(whole["chip_highest"],
                                                whole["cpu_f32"])
        net["chip_high_vs_chip_highest"] = l2_gap(whole["chip_high"],
                                                  whole["chip_highest"])
        row = {"seed": seed, "network": net, "layers": layers}
        rows.append(row)
        print(json.dumps(row), flush=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"workload": cell.name, "device": chip.device_kind,
                   "batch": args.batch, "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
