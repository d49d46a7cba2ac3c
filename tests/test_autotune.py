"""Autotuner (repro.kernels.autotune): plans, candidates, cache."""

import json

import pytest

from repro.kernels.autotune import (VMEM_BUDGET, ConvGeom, KernelPlan,
                                    candidate_plans, get_plan,
                                    heuristic_plan, lane_tiles, load_cache,
                                    measure, save_cache, tune,
                                    vmem_plan_bytes)

GEOMS = [
    ConvGeom(1, 12, 12, 256, 128, 3, 2),    # DCGAN d1 (padded)
    ConvGeom(1, 130, 258, 32, 16, 2, 2),    # MDE up1: prime-ish OH
    ConvGeom(2, 10, 9, 8, 16, 3, 1),        # plain conv kernel
    ConvGeom(1, 6, 10, 512, 512, 2, 2),     # deep channels, tiny spatial
]


@pytest.mark.parametrize("geom", GEOMS, ids=lambda g: g.key())
def test_heuristic_plan_valid(geom):
    p = heuristic_plan(geom)
    assert p.th >= 1
    assert geom.cin % p.tcin == 0
    assert geom.cout % p.tcout == 0
    # channel tiles Mosaic can lay out, and a step that fits VMEM
    assert p.tcin in lane_tiles(geom.cin)
    assert p.tcout in lane_tiles(geom.cout)
    assert vmem_plan_bytes(geom, p) <= VMEM_BUDGET


def test_heuristic_no_th1_collapse():
    """Prime OH must not collapse the row band to 1 (the old _pick_th
    pathology)."""
    geom = ConvGeom(1, 130, 258, 32, 16, 2, 2)     # OH = 129
    assert heuristic_plan(geom).th >= 4


@pytest.mark.parametrize("geom", GEOMS, ids=lambda g: g.key())
def test_candidate_plans_valid(geom):
    cands = candidate_plans(geom)
    assert 1 <= len(cands) <= 8
    assert heuristic_plan(geom) == cands[0]       # heuristic always tried
    for p in cands:
        assert geom.cin % p.tcin == 0
        assert geom.cout % p.tcout == 0


def test_from_deconv_geometry():
    g = ConvGeom.from_deconv(1, 8, 8, 256, 128, 5, 2)   # DCGAN d1
    assert (g.h, g.w, g.kt) == (12, 12, 3)              # P_I = KT-1 = 2
    assert g.oh == 10


def test_tune_persists_and_short_circuits(tmp_path):
    cache = str(tmp_path / "plans.json")
    geom = ConvGeom(1, 12, 12, 16, 8, 3, 2)
    target = KernelPlan(th=2, tcin=8, tcout=4)

    def runner(plan):
        return 0.1 if plan == target else 5.0

    won = tune(geom, runner, candidates=[KernelPlan(10, 16, 8), target],
               path=cache)
    assert won == target
    data = json.loads((tmp_path / "plans.json").read_text())
    entry = data["plans"][geom.key()]
    assert entry["source"] == "measured" and entry["th"] == 2

    def exploding(plan):
        raise AssertionError("tune() must not re-measure a cached plan")

    assert tune(geom, exploding, path=cache) == target
    assert get_plan(geom, path=cache) == target


def test_tune_skips_failing_candidates(tmp_path):
    cache = str(tmp_path / "plans.json")
    geom = ConvGeom(1, 12, 12, 16, 8, 3, 2)
    good = KernelPlan(th=4, tcin=16, tcout=8)

    def runner(plan):
        if plan != good:
            raise RuntimeError("backend rejected tile")
        return 1.0

    assert tune(geom, runner, candidates=[KernelPlan(8, 16, 8), good],
                path=cache) == good


def test_tune_raises_when_every_candidate_fails(tmp_path):
    """A backend that refuses every tile must surface its error, not
    hand back the heuristic plan as if it had been measured."""
    cache = str(tmp_path / "plans.json")
    geom = ConvGeom(1, 12, 12, 16, 8, 3, 2)

    def runner(plan):
        raise RuntimeError(f"Mosaic failed to compile {plan}")

    with pytest.raises(RuntimeError, match="Mosaic failed to compile"):
        tune(geom, runner, candidates=[KernelPlan(8, 16, 8),
                                       KernelPlan(4, 16, 8)], path=cache)
    assert geom.key() not in load_cache(cache)


def test_get_plan_falls_back_on_invalid_cache_entry(tmp_path):
    cache = str(tmp_path / "plans.json")
    geom = ConvGeom(1, 12, 12, 16, 8, 3, 2)
    # tcin=5 does not divide cin=16: entry must be ignored
    save_cache({geom.key(): {"th": 2, "tcin": 5, "tcout": 8,
                             "ms": 1.0, "source": "measured"}}, path=cache)
    assert get_plan(geom, path=cache) == heuristic_plan(geom)


def test_load_cache_tolerates_garbage(tmp_path):
    cache = tmp_path / "plans.json"
    cache.write_text("{not json")
    assert load_cache(str(cache)) == {}


def test_measure_returns_positive_ms():
    assert measure(lambda: sum(range(1000)), iters=3, warmup=1) >= 0.0


def test_corrupted_cache_recovers_on_next_save(tmp_path):
    """A torn/corrupt JSON cache must read as empty and be healed by the
    next atomic save — concurrent benchmark/serve processes can race."""
    import repro.kernels.autotune as at
    cache = tmp_path / "plans.json"
    cache.write_text('{"version": 1, "plans": {"b1_h12')   # torn write
    at._MEM.pop(str(cache), None)
    assert load_cache(str(cache)) == {}

    geom = ConvGeom(1, 12, 12, 16, 8, 3, 2)
    target = KernelPlan(th=2, tcin=8, tcout=4)
    won = tune(geom, lambda p: 0.1 if p == target else 5.0,
               candidates=[KernelPlan(4, 16, 8), target],
               path=str(cache))
    assert won == target

    at._MEM.pop(str(cache), None)              # force a real disk read
    data = json.loads(cache.read_text())       # valid JSON again
    assert data["plans"][geom.key()]["th"] == 2
    assert get_plan(geom, path=str(cache)) == target


def test_save_cache_atomic_no_stray_tmp_files(tmp_path):
    """save_cache goes through a unique mkstemp + os.replace: after any
    number of saves the directory holds exactly the cache file (a fixed
    shared .tmp name would let two writers interleave)."""
    cache = tmp_path / "plans.json"
    for i in range(3):
        save_cache({f"k{i}": {"th": 1, "tcin": 1, "tcout": 1}},
                   path=str(cache))
    assert [p.name for p in tmp_path.iterdir()] == ["plans.json"]
    data = json.loads(cache.read_text())
    assert data["plans"] == {"k2": {"th": 1, "tcin": 1, "tcout": 1}}


def test_save_cache_failure_leaves_old_cache_intact(tmp_path, monkeypatch):
    """If the JSON dump dies mid-write the previous cache file must
    survive untouched (the temp file is discarded, never renamed)."""
    import repro.kernels.autotune as at
    cache = tmp_path / "plans.json"
    save_cache({"good": {"th": 1, "tcin": 1, "tcout": 1}}, path=str(cache))

    class Boom(RuntimeError):
        pass

    def exploding_dump(*a, **k):
        raise Boom("disk full")

    monkeypatch.setattr(at.json, "dump", exploding_dump)
    with pytest.raises(Boom):
        save_cache({"bad": {}}, path=str(cache))
    monkeypatch.undo()
    at._MEM.pop(str(cache), None)
    assert [p.name for p in tmp_path.iterdir()] == ["plans.json"]
    assert load_cache(str(cache)) == {"good": {"th": 1, "tcin": 1,
                                               "tcout": 1}}


# ---------------------------------------------------------------------------
# Zero-copy PR additions: tw plan axis, tagged geometries, VMEM model
# ---------------------------------------------------------------------------

def test_plan_tw_defaults_and_cache_back_compat(tmp_path):
    """Pre-``tw`` cache entries (no "tw" key) load as full-width plans,
    and tw survives a save/load round-trip."""
    cache = str(tmp_path / "plans.json")
    geom = ConvGeom(1, 12, 12, 16, 8, 3, 2)
    save_cache({geom.key(): {"th": 2, "tcin": 8, "tcout": 4, "ms": 0.1,
                             "source": "measured",
                             "backend": __import__("jax").default_backend()}},
               path=cache)
    assert get_plan(geom, path=cache) == KernelPlan(th=2, tcin=8,
                                                    tcout=4, tw=0)

    target = KernelPlan(th=2, tcin=8, tcout=4, tw=6)
    won = tune(geom, lambda p: 0.1 if p == target else 5.0,
               candidates=[KernelPlan(4, 16, 8), target],
               path=cache, force=True)
    assert won == target
    import repro.kernels.autotune as at
    at._MEM.pop(cache, None)
    assert get_plan(geom, path=cache).tw == 6


def test_tagged_geom_keys_do_not_collide():
    """The backward's dx/dw launches tune under their own keys."""
    fwd = ConvGeom(2, 10, 10, 16, 8, 3, 1)
    dx = ConvGeom(2, 10, 10, 16, 8, 3, 1, tag="dx")
    dw = ConvGeom(2, 10, 10, 16, 8, 3, 1, tag="dw")
    keys = {fwd.key(), dx.key(), dw.key()}
    assert len(keys) == 3
    assert dx.key().endswith("_dx") and dw.key().endswith("_dw")


def test_vmem_budget_tiles_wide_layers():
    """A wide layer (fst-up1-like geometry) must not keep a full-width
    band + accumulator past the VMEM budget: the heuristic now tiles
    width/channels until the modelled footprint fits."""
    from repro.kernels.autotune import VMEM_BUDGET, vmem_plan_bytes
    geom = ConvGeom(1, 130, 258, 64, 64, 2, 2)      # wide, deep-ish
    plan = heuristic_plan(geom)
    assert vmem_plan_bytes(geom, plan) <= VMEM_BUDGET
    # and the model counts more than the filter block: a full-width,
    # full-channel plan on this geometry is over budget
    full = KernelPlan(th=plan.th, tcin=64, tcout=64, tw=0)
    assert (vmem_plan_bytes(geom, full) > VMEM_BUDGET
            or plan == full)


def test_candidates_include_width_tiles_on_wide_geoms():
    geom = ConvGeom(1, 130, 1026, 32, 16, 2, 2)     # ow = 1025
    cands = candidate_plans(geom, max_candidates=8)
    assert any(p.tw for p in cands), "wide geometry should offer tw tiles"
    # TPU launches only ever see budget-clean candidates; off-TPU the
    # full pool stays (no VMEM in interpret mode, measurement decides).
    from repro.kernels.autotune import VMEM_BUDGET, vmem_plan_bytes
    for p in candidate_plans(geom, max_candidates=8,
                             enforce_budget=True):
        assert vmem_plan_bytes(geom, p) <= VMEM_BUDGET


# (bucket, input hw, cin, cout, k) at the cells' buckets, all stride 2.
_CELL_LAYERS = {
    "dcgan_d3": (256, (32, 32), 64, 3, 5),
    "mde_up1": (16, (128, 256), 32, 16, 3),
    "pix2pix_u8": (32, (128, 128), 128, 3, 4),
    "dcgan_d1": (256, (8, 8), 256, 128, 5),
    "mde_up4": (16, (16, 32), 256, 128, 3),
    "pix2pix_u5": (32, (16, 16), 1024, 256, 4),
}


def _cell_geom(name):
    from repro.core.deconv import same_deconv_pads
    b, hw, cin, cout, k = _CELL_LAYERS[name]
    return ConvGeom.from_deconv(b, *hw, cin, cout, k, 2,
                                padding=same_deconv_pads(k, 2))


@pytest.mark.parametrize("name", ["dcgan_d3", "mde_up1", "pix2pix_u8"])
def test_vmem_model_counts_packed_phase_lanes(name, monkeypatch):
    """A phase-packed launch's filter block and accumulator take
    round_up(s^2 * tcout, 128) lanes, not s^2 tiles of 128: the model
    differs from the per-phase count by exactly those lanes, and the
    heuristic plan stays within the budget."""
    import repro.kernels.autotune as at
    geom = _cell_geom(name)
    plan = heuristic_plan(geom)
    assert plan.tcout == geom.cout < 128
    packed = vmem_plan_bytes(geom, plan)
    assert packed <= VMEM_BUDGET
    monkeypatch.setattr(at, "phase_packed", lambda phases, tcout: False)
    per_phase = vmem_plan_bytes(geom, plan)
    cols = -(-((plan.tw or geom.ow) + 1) // 8) * 8
    lane_bytes = (2 * geom.kt ** 2 * -(-plan.tcin // 8) * 8 * 4
                  + (plan.th + 1) * cols * 4)
    saved = 4 * 128 - -(-4 * geom.cout // 128) * 128
    assert per_phase - packed == saved * lane_bytes


@pytest.mark.parametrize("name,nbytes", [
    ("dcgan_d1", 5_873_664), ("mde_up4", 7_913_472),
    ("pix2pix_u5", 8_175_616)])
def test_vmem_model_unchanged_for_wide_tiles(name, nbytes):
    """tcout >= 128: one phase per lane tile, as before packing."""
    geom = _cell_geom(name)
    plan = heuristic_plan(geom)
    assert plan.tcout >= 128
    assert vmem_plan_bytes(geom, plan) == nbytes
