"""What csi50k-mesh4 brought: the sharded programs' names, the
configuration's load-time refusal and checker, the cell's four new
readers on hand-built runs, and the cell itself rehearsed on four
virtual devices."""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.loader import load_json, load_module  # noqa: E402

CELL = "csi50k-drain-mesh4"
# the static arguments each kind's builder takes after the mesh
KIND_STATICS = {"scan": (), "multi": (64,),
                "multi_chained": (64,), "multi_compact": (64, 5),
                "multi_compact_chained": (64, 5), "scatter": ()}


# ------------------------------------------------------- program names

def test_every_sharded_kind_has_its_statics_here():
    from nomad_tpu.ops.engine import SHARDED_KINDS
    assert set(KIND_STATICS) == set(SHARDED_KINDS)


@pytest.mark.parametrize("kind", sorted(KIND_STATICS))
def test_sharded_program_is_named(kind):
    from nomad_tpu.ops import engine
    from nomad_tpu.parallel import mesh as pmesh
    fn = engine._sharded_fn(engine._default_mesh(), kind,
                            *KIND_STATICS[kind])
    assert fn.__name__ in pmesh.PROGRAM_NAMES
    assert fn.__name__.startswith("place_") != (kind == "scatter")
    assert "sharded" in fn.__name__
    # no single-device program carries the name
    from nomad_tpu.ops import select
    assert not hasattr(select, fn.__name__)


def test_program_names_are_exactly_the_programs_built():
    from nomad_tpu.ops import engine
    from nomad_tpu.parallel import mesh as pmesh
    built = {engine._sharded_fn(engine._default_mesh(), kind,
                                *statics).__name__
             for kind, statics in KIND_STATICS.items()}
    assert built == set(pmesh.PROGRAM_NAMES)
    assert len(pmesh.PROGRAM_NAMES) == len(KIND_STATICS)


# ---------------------------------------------------- the configuration

def mesh4():
    cfg = load_json("configs", "csi50k-mesh4")
    return dict(cfg, **cfg["rehearse"]), load_module("configs",
                                                     "csi50k-mesh4")


def test_configuration_hands_on_csi50ks_builders():
    cfg, mod = mesh4()
    twin = load_module("configs", "csi50k")
    a, b = mod.build_fleet(cfg, 2147483683), twin.build_fleet(
        dict(load_json("configs", "csi50k"), **cfg["rehearse"]), 2147483683)
    assert [n.id for n in a[0]] == [n.id for n in b[0]] and a[1] == b[1]
    assert mod.make_job(cfg, 7) == twin.make_job(cfg, 7)
    base = load_json("configs", "csi50k")
    assert cfg["guarantees"][:5] == base["guarantees"]
    assert all(cfg[k] == base[k] for k in base
               if k not in ("name", "source", "server", "reduced",
                            "reduced_why", "assumed", "layout",
                            "guarantees", "main_layer", "nodes",
                            "count_per_job"))


def test_a_program_with_anonymous_sharded_programs_is_refused_at_load(
        monkeypatch, capsys):
    from nomad_tpu.parallel import mesh as pmesh
    monkeypatch.delattr(pmesh, "PROGRAM_NAMES")
    with pytest.raises(SystemExit) as e:
        load_module("configs", "csi50k-mesh4")
    assert e.value.code == 5
    assert "PROGRAM_NAMES" in capsys.readouterr().err


def _sound(cfg, nodes, jobs):
    zones = cfg["zones"]
    ids = [n.id for n in nodes]
    return {j["ID"]: [ids[(i % zones) + zones * k]
                      for k in range(cfg["count_per_job"])]
            for i, j in enumerate(jobs)}


@pytest.mark.parametrize("fault", ["none", "zone", "over-full", "short"])
def test_check_names_a_planted_fault(fault):
    cfg, mod = mesh4()
    nodes, fleet = mod.build_fleet(cfg, 2147483683)
    jobs = [mod.make_job(cfg, i) for i in range(10)]
    placed = _sound(cfg, nodes, jobs)
    needle = None
    if fault == "zone":
        placed[jobs[0]["ID"]] = placed[jobs[1]["ID"]]
        needle = "outside their volume's zone"
    elif fault == "short":
        placed[jobs[2]["ID"]] = placed[jobs[2]["ID"]][:-1]
        needle = "committed != asked"
    elif fault == "over-full":
        # every job of zone 0 on ONE node, at an ask that fills it
        cfg = dict(cfg, ask_cpu_mhz=2000)
        jobs = [json.loads(json.dumps(j)) for j in jobs]
        for j in jobs:
            j["TaskGroups"][0]["Tasks"][0]["Resources"]["CPU"] = 2000
        placed = {j["ID"]: [nodes[i % cfg["zones"]].id]
                  * cfg["count_per_job"] for i, j in enumerate(jobs)}
        needle = "over resources - reserved"
    got = mod.check(cfg, fleet, jobs, placed)
    if needle is None:
        assert got == []
    else:
        assert any(needle in f for f in got), got


# ------------------------------------------------------------- readers

def reader(name):
    return load_module("layer_metrics", name)


def fake_run(programs=None, waves=(), chips=4, **more):
    cfg = load_json("configs", "csi50k-mesh4")
    return SimpleNamespace(
        cell=dict(load_json("workloads", CELL), chips=chips), cfg=cfg,
        device={"kind": "TPU v5 lite"},
        trace={"programs": programs} if programs else {},
        tap_window={"waves": list(waves), "intervals": {}}, tmp="/nonexistent",
        **more)


@pytest.mark.parametrize("chips", [2, 4])
def test_sharded_roofline_is_the_one_chip_formula_over_chips(chips):
    from benchmark import kernel_cost, peaks
    waves = [{"items": 64}] * 3
    # two programs, launches summed over the chips used
    run = fake_run({"jit_place_multi_compact_sharded": (chips, 0.004 * chips),
                    "jit_place_multi_compact_sharded_chained":
                        (5 * chips, 0.002 * 5 * chips),
                    "jit_scatter_add_sharded": (chips, 1.0)}, waves, chips)
    got = reader("place_multi_compact_sharded_roofline").read(run)
    measured = (0.004 + 5 * 0.002) / 6
    one = kernel_cost.roofline(
        kernel_cost.compact_launch(50_000, 5, 64.0),
        peaks.peaks_for("TPU v5 lite"), measured)
    assert got == pytest.approx(one["share_pct"] / chips)
    # the one-chip reader on the same run would count the same programs
    twin = reader("place_multi_compact_roofline").read(run)
    assert twin == pytest.approx(one["share_pct"])


def test_sharded_roofline_reads_nothing_without_a_sharded_launch():
    run = fake_run({"jit_place_multi_compact_packed": (6, 0.024)},
                   [{"items": 64}])
    assert reader("place_multi_compact_sharded_roofline").read(run) is None
    assert reader("place_multi_compact_sharded_roofline").read(
        fake_run()) is None


def two_chip_trace():
    gather = ("%all-gather.3 = f32[5,256]{1,0} all-gather(f32[5,64]{1,0} "
              "%p), replica_groups={}")
    return {"ops_read": True, "anchor_s": 0.0, "chips": {
        0: {"busy": [], "programs": {}, "ops": {
            gather: 0.002, "%fusion.8 = f32[8] fusion(f32[8] %x)": 0.006,
            "%all-reduce-start.1 = s32[] all-reduce-start(s32[] %y)": 0.001,
            "%all-reduce-done.1 = s32[] all-reduce-done(s32[] %z)": 0.001}},
        1: {"busy": [], "programs": {}, "ops": {
            gather: 0.004, "%sort.11 = f32[64] sort(f32[64] %k)": 0.006}},
        2: {"busy": [], "programs": {}, "ops": {gather: 9.0}}}}


def test_collective_share_over_the_chips_used():
    mod = reader("mesh4.collective_share")
    pct, per_chip = mod.share(two_chip_trace(), 2)
    assert pct == pytest.approx(100.0 * 0.008 / 0.020)
    assert [(c, round(t, 6), round(k, 6)) for c, t, k in per_chip] == [
        (0, 0.010, 0.004), (1, 0.010, 0.004)]
    assert 0.0 <= pct <= 100.0


@pytest.mark.parametrize("name,verdict", [
    ("%all-gather.3 = f32[5,256]{1,0} all-gather(f32[5,64] %p)", True),
    ("%ar = s32[] all-reduce-start(s32[] %y), to_apply=%add", True),
    ("%rs.1 = f32[4] reduce-scatter(f32[16] %y)", True),
    ("%cp = f32[4] collective-permute-done(f32[4] %y)", True),
    ("%a2a = f32[4] all-to-all(f32[4] %y)", True),
    ("%reduce.4 = f32[] reduce(f32[64] %y, f32[] %c)", False),
    ("%fusion.8 = f32[8] fusion(f32[8] %all-gather.3)", False),
    ("%while.2", False)])
def test_collectives_told_by_hlo_kind(name, verdict):
    assert reader("mesh4.collective_share").is_collective(name) is verdict


def test_collective_share_reads_nothing_where_no_op_was_read():
    mod = reader("mesh4.collective_share")
    unread = two_chip_trace()
    unread["ops_read"] = False
    assert mod.share(unread, 2) is None
    assert mod.share({"ops_read": True, "chips": {
        0: {"ops": {}}}}, 1) is None
    # a run with no trace, and one whose trace left no file
    assert mod.read(fake_run()) is None
    assert mod.read(fake_run({"jit_place_x": (1, 0.1)})) is None


def test_collective_kb_per_wave_is_the_wave_records_mean():
    mod = reader("mesh4.collective_kb_per_wave")
    run = fake_run(waves=[{"items": 64, "collective_bytes": 2048},
                          {"items": 64, "collective_bytes": 4096},
                          {"items": 64}])
    assert mod.read(run) == pytest.approx(3.0)
    assert mod.read(fake_run(waves=[{"items": 64}])) is None


def test_launch_ms_per_wave_from_the_stage_totals():
    mod = reader("mesh4.launch_ms_per_wave")
    c0 = {"stage_totals": {"mesh_launch": 0.5, "dispatch": 1.0},
          "stage_counts": {"mesh_launch": 10, "dispatch": 10}}
    c1 = {"stage_totals": {"mesh_launch": 0.8, "dispatch": 2.0},
          "stage_counts": {"mesh_launch": 70, "dispatch": 70}}
    assert mod.read(fake_run(c0=c0, c1=c1)) == pytest.approx(5.0)
    # first seen inside the window
    bare = {"stage_totals": {}, "stage_counts": {}}
    assert mod.read(fake_run(c0=bare, c1=c1)) == pytest.approx(0.8e3 / 70)
    # an engine with no mesh records none
    assert mod.read(fake_run(c0=bare, c1=bare)) is None


# ---------------------------------------------------------- the files

def test_cell_and_benchmark_json_agree():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = load_json("workloads", CELL)
    (entry,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert entry["chips"] == cell["chips"] == 4
    assert entry["traffic"] == cell["traffic"] == "drain384"
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= len(
        bench["workloads"]) // 2
    listed = [m["name"] for m in bench["per_layer"]
              if m.get("workloads") == [CELL]]
    assert listed == cell["per_layer"] and len(listed) == 16
    (e2e,) = [m for m in bench["end_to_end"] if m["name"] == "placed_per_s"]
    assert e2e["workloads"] == ["csi50k-drain", CELL]
    # one cycle's six waves, one execution a chip
    assert cell["trace_min_launches"] == 6 * cell["chips"]
    for name in cell["per_layer"]:
        mod = reader(cell["readers"].get(name, name))
        assert callable(mod.read) and mod.UNIT


def _cpu_env(**more):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", **more)
    return env


def test_selftest_is_green():
    p = subprocess.run([sys.executable, "-m", "benchmark.selftest"],
                       cwd=REPO, env=_cpu_env(), capture_output=True,
                       text=True, timeout=600)
    assert p.returncode == 0, p.stdout[-2000:]
    assert p.stdout.strip().endswith("0 failed")


def test_cell_rehearses_on_four_virtual_devices():
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.selftest", "rehearse-one", CELL],
        cwd=REPO, capture_output=True, text=True, timeout=600,
        env=_cpu_env(
            XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert p.returncode == 0, (p.stdout[-2000:], p.stderr[-2000:])
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert out["device"] == {"platform": "cpu", "kind": "cpu", "count": 4,
                             "memory_peak_bytes": 0}
    assert set(out["metrics"]) == {"placed_per_s", "setup_s"}


def test_cell_refuses_a_host_with_one_device():
    # what `selftest rehearse` meets today: one CPU device (PERF.md 7)
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.selftest", "rehearse-one", CELL],
        cwd=REPO, env=_cpu_env(), capture_output=True, text=True,
        timeout=600)
    assert p.returncode == 2 and "needs 4" in p.stderr
