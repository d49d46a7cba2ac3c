"""Benchmark driver: one module per paper table/figure + roofline report.

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run --only tables123,procmodel
  PYTHONPATH=src python -m benchmarks.run --json out.json   # + JSON dump

shard_bench needs four devices: on a CPU host run the driver under
XLA_FLAGS=--xla_force_host_platform_device_count=4.
"""

import argparse
import json
import time


class Report:
    """Plain-text table printer; keeps CSV lines and structured tables
    (every section/header/row/note) for the --json dump."""

    def __init__(self):
        self.csv = []
        self.tables = []

    def _table(self):
        if not self.tables:
            self.tables.append({"title": "", "header": None,
                                "rows": [], "notes": []})
        return self.tables[-1]

    def section(self, title):
        print(f"\n=== {title} ===")
        self._cols = None
        self.tables.append({"title": str(title), "header": None,
                            "rows": [], "notes": []})

    def header(self, cols):
        self._cols = [str(c) for c in cols]
        print(" | ".join(f"{c:>14}" if i else f"{c:<24}"
                         for i, c in enumerate(self._cols)))
        self._table()["header"] = list(self._cols)

    def row(self, vals):
        vals = [str(v) for v in vals]
        print(" | ".join(f"{v:>14}" if i else f"{v:<24}"
                         for i, v in enumerate(vals)))
        self.csv.append(",".join(vals))
        self._table()["rows"].append(vals)

    def note(self, text):
        print(f"  -> {text}")
        self._table()["notes"].append(str(text))

    def to_json(self):
        return {"tables": self.tables}

    def dump_json(self, path):
        with open(path, "w") as f:
            json.dump(self.to_json(), f, indent=1)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated module names")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="dump every report table as JSON to PATH")
    args = ap.parse_args()

    from benchmarks import (commodity, kernel_bench, loadgen, nd_bench,
                            procmodel, quant_bench, roofline_report,
                            sd_roofline, serve_bench, shard_bench,
                            table4_ssim, tables123, train_bench)
    mods = {"tables123": tables123, "table4_ssim": table4_ssim,
            "procmodel": procmodel, "commodity": commodity,
            "kernel_bench": kernel_bench, "sd_roofline": sd_roofline,
            "serve_bench": serve_bench, "train_bench": train_bench,
            "nd_bench": nd_bench, "quant_bench": quant_bench,
            "loadgen": loadgen, "shard_bench": shard_bench,
            "roofline_report": roofline_report}
    wanted = (args.only.split(",") if args.only else list(mods))
    report = Report()
    t0 = time.time()
    for name in wanted:
        t1 = time.time()
        mods[name].run(report)
        print(f"  [{name}: {time.time()-t1:.1f}s]")
    if args.json:
        report.dump_json(args.json)
        print(f"report tables dumped to {args.json}")
    # Consolidated cross-suite headline (speedups + parity flags) from
    # whatever BENCH_*.json artifacts exist on disk — the machine-
    # readable perf trajectory across PRs.
    from benchmarks import summary as bench_summary
    bench_summary.write_summary()
    print(f"consolidated summary written to {bench_summary.OUT_JSON}")
    print(f"\nall benchmarks done in {time.time()-t0:.1f}s")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
