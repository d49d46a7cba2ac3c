"""DP x MP serving grid: channel-sharded plans vs data-parallel only.

The scale-out claim behind ``serve_gen --dp --mp``: on a fixed device
budget, a (data x model) mesh beats DP-only for *launch latency* —
a single request on ``--dp 4`` pads its batch to the dp multiple
(4x the work for one sample), while ``--mp 4`` runs the same request
with every shardable deconv layer's Cout split four ways and one
all-gather per layer.  Per paper net this sweeps the full degree-4
grid

  dp1     — single device, the unsharded reference (parity anchor)
  dp4     — data-parallel only (batches shard over 'data')
  dp2xmp2 — the hybrid cell
  mp4     — model-parallel only (Cout shards over 'model')

and records median group-launch wall time for a 1-request and an
8-request group, plus per-config parity (max |delta| vs dp1 on the
same latents — engines bind identical checkpoints, so mesh configs
must reproduce the single-device images).

The grid runs in the calling process over its first four devices: the
chips of a four-chip host, or on a CPU host the virtual devices that
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` creates (the
device count is fixed when jax initialises, so set it in the
environment).  Results go to BENCH_shard.json for the cross-PR
trajectory.

  PYTHONPATH=src XLA_FLAGS=--xla_force_host_platform_device_count=4 \
      python -m benchmarks.shard_bench              # full
  ... python -m benchmarks.shard_bench --nets gpgan,voxgan
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ALL_NETS = ("dcgan", "sngan", "artgan", "gpgan", "mde", "fst", "voxgan")
CONFIGS = (("dp1", 1, 1), ("dp4", 4, 1), ("dp2xmp2", 2, 2),
           ("mp4", 1, 4))
OUT_JSON = "BENCH_shard.json"
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def sweep(nets=ALL_NETS, iters=3, reduced=False, out=OUT_JSON,
          report=None):
    import jax
    import numpy as np
    from repro.kernels.autotune import measure
    from repro.launch.serve_gen import GenServer, reduced_specs

    if jax.device_count() < 4:
        raise RuntimeError(
            f"shard_bench needs 4 devices, have {jax.device_count()} "
            "(on a CPU host set XLA_FLAGS="
            "--xla_force_host_platform_device_count=4)")
    specs = reduced_specs() if reduced else None
    if reduced:
        nets = list(specs)

    results = {"jax_backend": jax.default_backend(),
               "devices": jax.device_count(),
               "configs": [c[0] for c in CONFIGS], "nets": {}}
    for net in nets:
        rec = {"configs": {}, "parity_ok": True}
        ref_out = {}
        for cname, dp, mp in CONFIGS:
            srv = GenServer(nets=[net], specs=specs, backend="auto",
                            seed=0, dp=dp, mp=mp)
            z1 = [r.latent for r in srv.random_requests(net, 1, seed=5)]
            z8 = [r.latent for r in srv.random_requests(net, 8, seed=6)]
            y1 = np.asarray(srv.run_group(net, z1))     # also warms b1
            y8 = np.asarray(srv.run_group(net, z8))     # ... and b8
            if cname == "dp1":
                ref_out = {"1": y1, "8": y8}
                maxabs = 0.0
            else:
                maxabs = max(
                    float(np.max(np.abs(y1 - ref_out["1"]))),
                    float(np.max(np.abs(y8 - ref_out["8"]))))
            ok = maxabs <= 1e-5
            rec["parity_ok"] = rec["parity_ok"] and ok
            t1 = measure(lambda: jax.block_until_ready(
                srv.run_group(net, z1)), iters=iters, warmup=1)
            t8 = measure(lambda: jax.block_until_ready(
                srv.run_group(net, z8)), iters=iters, warmup=1)
            rec["configs"][cname] = {
                "launch_ms": round(t1, 3), "batch8_ms": round(t8, 3),
                "parity_maxabs": maxabs, "parity_ok": ok,
                "compiles": srv.compile_count,
            }
        dp_only = rec["configs"]["dp4"]["launch_ms"]
        best_mesh = min(rec["configs"][c]["launch_ms"]
                        for c in ("dp2xmp2", "mp4"))
        rec["launch_speedup_mesh_vs_dp"] = (
            round(dp_only / best_mesh, 3) if best_mesh else None)
        results["nets"][net] = rec
        print(f"  {net}: mesh-vs-dp launch speedup "
              f"{rec['launch_speedup_mesh_vs_dp']}x "
              f"parity={'OK' if rec['parity_ok'] else 'FAIL'}",
              file=sys.stderr)

    if report is not None:
        report.section("DP x MP serving grid — sharded plans vs "
                       "DP-only (4 devices)")
        report.header(["net", "config", "launch_ms", "batch8_ms",
                       "parity"])
    for net, rec in results["nets"].items():
        for cname, row in rec["configs"].items():
            line = [net, cname, row["launch_ms"], row["batch8_ms"],
                    "OK" if row["parity_ok"] else "FAIL"]
            if report is not None:
                report.row(line)
            else:
                print("  " + " | ".join(str(v) for v in line))
    if out:
        with open(os.path.join(_REPO, out) if not os.path.isabs(out)
                  else out, "w") as f:
            json.dump(results, f, indent=1, sort_keys=True)
        msg = f"shard sweep written to {out}"
        if report is not None:
            report.note(msg)
        else:
            print(msg)
    return results


def run(report):
    """benchmarks.run hook: reduced specs + two iters, so the full
    driver stays fast; the standalone main measures the paper nets."""
    sweep(reduced=True, iters=2, out=None, report=report)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--nets", default=",".join(ALL_NETS))
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--reduced", action="store_true",
                    help="dryrun-sized specs (ci smoke)")
    ap.add_argument("--out", default=OUT_JSON)
    args = ap.parse_args(argv)
    sweep(nets=tuple(args.nets.split(",")), iters=args.iters,
          reduced=args.reduced, out=args.out)


if __name__ == "__main__":
    main()
