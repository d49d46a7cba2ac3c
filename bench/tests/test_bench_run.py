"""``bench/run.py`` end to end on the CPU, at a test size.

Without a TPU the command exits non-zero and prints no result.  With the
look for the chip replaced, a whole run (set-up, window, check against
the reference, result line) drives the real serving path on the CPU's
XLA backend: it must come out correct, and must come out not correct
when the reference computed in three bfloat16 passes (the control) takes
the program's place, and under each fault a serving cell can have.
"""

import json
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import harness  # noqa: E402
import run  # noqa: E402

SEED = 2 ** 32 + 99


def _no_tpu_run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "dcgan.offline",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=cwd, timeout=300)


def test_exits_nonzero_without_tpu():
    out = _no_tpu_run(ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _no_tpu_run(tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def _small(name, **workload):
    cell = harness.Cell.load(name)
    cell.workload = dict(cell.workload, max_batch=4, pool=16, sample=16,
                         reference_block=4, **workload)
    return cell


@pytest.fixture
def cpu_run(monkeypatch):
    """Run one cell on the CPU: the look for the chip returns the CPU
    devices, the peaks are v5e's, and no compile cache is written."""
    v5e = harness.read_json(BENCH, "peaks.json")["TPU v5 lite"]
    monkeypatch.setattr(harness, "peaks_for", lambda kind: v5e)
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: None)

    def go(cell, trace=0, seconds=1.0):
        args = run.parse(["--workload", cell.name, "--seed", str(SEED),
                          "--seconds", str(seconds), "--trace", str(trace)])
        return run.execute(args, find_chips=lambda n: jax.devices(),
                           cell=cell)
    return go


@pytest.mark.parametrize("trace", [0, 1])
def test_result_line_schema(cpu_run, trace):
    cell = _small("dcgan.offline")
    line = cpu_run(cell, trace=trace)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    json.loads(json.dumps(line))
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    want = {m["name"]: m["unit"] for m in
            (cell.per_layer if trace else cell.end_to_end)}
    for name, m in line["metrics"].items():
        assert m["unit"] == want[name] and m["value"] > 0
    if trace:
        # The CPU has no device trace: only the host-side metrics read.
        assert set(line["metrics"]) == {"launch_ms.offline", "mfu_pct"}
    else:
        assert set(line["metrics"]) == {"images_per_s", "setup_s"}
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"}


def test_open_loop_traffic(cpu_run):
    """Poisson arrivals through the same run: every request due in the
    window is served and compared (no cell of BENCHMARK.json sends them
    yet; `bench/sweep.py` does)."""
    import traffic
    cell = _small("dcgan.offline", drain_s=60)
    cell.traffic = traffic.load("poisson", {"arrival": "poisson",
                                            "rate_per_s": 40.0})
    line = cpu_run(cell)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] == 40 and line["failed"] == 0


def _config_of(net, name):
    """A configuration file's content for a net of the program."""
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.core.accounting import WORKLOADS
    spec = WORKLOADS[net]()
    layers = [{"kind": l.kind, "name": l.name, "cin": l.cin, "cout": l.cout}
              if l.kind == "fc" else
              {"kind": l.kind, "name": l.name, "cin": l.cin, "cout": l.cout,
               "k": l.k, "s": l.s, "in_hw": list(l.in_hw),
               "padding": l.padding} for l in spec.layers]
    return {"name": name, "net": net, "dtype": "float32", "backend": "auto",
            "reference": "sequential", "input": {"dist": "normal"},
            "final_tanh": spec.final_tanh, "layers": layers,
            "limits": {"l2_rel_err": 2e-6}}


def test_a_cell_serves_a_mix_of_nets(cpu_run):
    """A workload that lists several configurations with their shares is
    served as one mix, and each configuration is checked on its own."""
    cell = _small("dcgan.offline")
    cell.configs = {"dcgan_f32": cell.configs["dcgan_f32"],
                    "sngan_test": _config_of("sngan", "sngan_test")}
    cell.shares = {"dcgan_f32": 0.7, "sngan_test": 0.3}
    line = cpu_run(cell)
    assert line["correct"] is True, line["checks"]
    assert {"l2_rel_err.dcgan_f32", "l2_rel_err.sngan_test"} <= set(
        line["checks"])
    bench = harness.Bench.__new__(harness.Bench)
    bench.nets, bench._net_key = ["dcgan", "sngan"], harness._seed_key(SEED,
                                                                      0x4E7)
    bench._cum = harness.np.array([0.7, 1.0])
    picks = [bench.net_of(r) for r in range(4000)]
    assert picks.count("sngan") / 4000 == pytest.approx(0.3, abs=0.03)


def _patch_run_group(monkeypatch, wrap):
    from repro.launch.serve_gen import GenServer
    orig = GenServer.run_group
    monkeypatch.setattr(GenServer, "run_group",
                        lambda self, net, latents: wrap(
                            self, net, latents, orig(self, net, latents)))


def test_control_is_not_correct(cpu_run, monkeypatch):
    """The reference at "high" (three bfloat16 passes) in the program's
    place fails the limit."""
    cell = _small("dcgan.offline")
    ref = harness.load_module(os.path.join(BENCH, "references",
                                           "sequential.py"))
    config = cell.configs["dcgan_f32"]
    control = jax.jit(lambda p, x: ref.forward(config, p, x, "high"))

    def wrap(server, net, latents, y):
        _, params = server.model(net)
        return control(params, jnp.stack([jnp.asarray(z) for z in latents]))
    _patch_run_group(monkeypatch, wrap)
    line = cpu_run(cell)
    assert line["correct"] is False
    err = line["checks"]["l2_rel_err.dcgan_f32"]
    assert err["value"] > err["limit"]


FAULTS = {
    # one value of every answer altered where it is produced
    "answer_altered": lambda y: y.at[:, 0, 0, 0].add(1e-3),
    # answers handed to the wrong requests
    "answers_swapped": lambda y: y[::-1],
    # half of the batch left out
    "half_batch_dropped": lambda y: y.at[y.shape[0] // 2:].set(0.0),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_faults_are_not_correct(cpu_run, monkeypatch, fault):
    _patch_run_group(monkeypatch,
                     lambda server, net, latents, y: FAULTS[fault](y))
    line = cpu_run(_small("dcgan.offline"))
    assert line["correct"] is False, line["checks"]


def test_compile_in_window_fails(cpu_run, monkeypatch):
    shapes = iter(range(1, 10 ** 6))

    def wrap(server, net, latents, y):
        jnp.zeros(next(shapes)).block_until_ready()   # a new shape each time
        return y
    _patch_run_group(monkeypatch, wrap)
    with pytest.raises(RuntimeError, match="compiled inside the measured"):
        cpu_run(_small("dcgan.offline"))
