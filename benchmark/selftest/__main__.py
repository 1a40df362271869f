"""Self-checks of the yardstick, on the CPU:

    python3 -m benchmark.selftest             # arithmetic, reducer, checker, files
    python3 -m benchmark.selftest rehearse    # + every cell end to end, tiny

The rehearsal runs each cell under benchmark/workloads through benchmark.run's
own code on `JAX_PLATFORMS=cpu`, at the tiny size its configuration's and
its traffic file's `rehearse` keys give.  It is reachable from
here alone, never by a fallback of the benchmark's command; its output
names platform `cpu`, and none of its numbers is a speed.
"""

from __future__ import annotations

import json
import math
import os
import re
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")

failures: list = []


def check(ok: bool, what: str) -> None:
    print(("ok    " if ok else "FAIL  ") + what)
    if not ok:
        failures.append(what)


def raises(exc, fn, *args) -> bool:
    try:
        fn(*args)
    except exc:
        return True
    return False


# ---------------------------------------------------------------- pieces

def check_stats() -> None:
    from benchmark import stats

    check(stats.median([3, 1, 2]) == 2 and stats.median([4, 1, 3, 2]) == 2.5,
          "median of odd and even counts")
    check(raises(stats.TooFewSamples, stats.median, []), "median of nothing")
    check(stats.min_samples(0.99) == 1000 and stats.min_samples(0.95) == 200,
          "ten samples beyond: p99 needs 1,000 readings, p95 200")
    xs = list(range(1, 1001))
    check(stats.percentile(xs, 0.99) == 990 and stats.percentile(xs, 0.5)
          == 500, "nearest-rank percentiles of 1..1000")
    check(raises(stats.TooFewSamples, stats.percentile, xs[:999], 0.99),
          "p99 of 999 readings is refused")
    check(stats.percentile_or_none(xs[:999], 0.99) is None,
          "a per-layer reader gets nothing instead")
    check(stats.percentile(xs[:-1] + [math.inf], 0.99) == 990,
          "a failed job read as +inf sits past the percentile")
    check(abs(stats.spread([9, 10, 10, 10, 11, 12]) - 0.075) < 1e-9,
          "spread = interquartile distance over the median")


def check_schedule() -> None:
    from benchmark import schedule

    a = schedule.poisson(7, 100.0, 30.0)
    b = schedule.poisson(7, 100.0, 30.0)
    c = schedule.poisson(8, 100.0, 30.0)
    check(schedule.encode(a) == schedule.encode(b),
          "one seed gives one schedule, byte for byte")
    check(schedule.encode(a) != schedule.encode(c), "another seed differs")
    check(a == sorted(a) and 0 <= a[0] and a[-1] < 30.0
          and 2700 < len(a) < 3300, "due times ordered, inside the span, "
          "about rate x seconds of them")


def check_trace() -> None:
    from benchmark import trace_reduce as tr

    path = os.path.join(HERE, "data", "small_trace.xplane.pb")
    red = tr.reduce_trace(path)
    s = tr.summarize(red)
    # read by hand from the recorded trace (TPU v5e, 3 launches of
    # jit_place_probe with ~20 ms idle between them)
    progs = s["programs"]
    check(list(progs) == ["jit_place_probe"] and progs[
        "jit_place_probe"][0] == 3, "three launches of one program")
    check(abs(progs["jit_place_probe"][1] - 268292e-9) < 2e-9,
          "kernel sum 268.292 us")
    check(abs(s["busy_s"] - 268292e-9) < 2e-9, "busy 268.292 us")
    check(abs(s["window_s"] - 42901810e-9) < 2e-9,
          "window 42.90181 ms (first launch's start to the last's end)")
    check(abs(red["anchor_s"] - 44953309e-9) < 1e-9, "anchor found")
    check(len(s["idle"]) == 2 and abs(
        tr.total(s["idle"]) - (s["window_s"] - s["busy_s"])) < 1e-12,
        "two idle gaps that sum to window - busy")
    check(s["device_ops"][0][0].startswith("%fusion")
          and len(s["device_ops"]) <= 10, "heaviest op is the fusion")
    # clipped to a window that holds the middle launch alone
    mid = red["chips"][0]["busy"][1]
    s2 = tr.summarize(red, [(mid[0] - 1e-3, mid[1] + 1e-3)])
    check(abs(s2["busy_s"] - (mid[1] - mid[0])) < 1e-12
          and abs(s2["window_s"] - (mid[1] - mid[0] + 2e-3)) < 1e-9,
          "busy clipped to the timed window")
    named = tr.attribute_gaps(s2["idle"], {
        "materialize": [(mid[0] - 1e-3, mid[0] - 0.2e-3)],
        "commit": [(mid[1] + 0.5e-3, mid[1] + 0.6e-3)]})
    check(dict(named).keys() == {"host:materialize", "host:commit"},
          "gaps named by the stage that covers most of each")
    check(tr.op_name("%fusion.8 = f32[512,512]{1,0} fusion(f32[512,512] "
                     "%copy.11), kind=kOutput") == "%fusion.8 fusion",
          "op names shortened")


def check_cost_and_peaks() -> None:
    from benchmark import kernel_cost, peaks

    check(raises(KeyError, peaks.peaks_for, "TPU v9"),
          "a device not in the table of peaks is an error")
    p = peaks.peaks_for("TPU v5 lite")
    check(p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9,
          "v5e peaks as published")
    r = kernel_cost.roofline({"ops": 197e12, "bytes": 819e9 / 2}, p, 2.0)
    check(r["bound"] == "compute" and abs(r["share_pct"] - 50.0) < 1e-9,
          "roofline share = least time over measured, larger bound named")
    c = kernel_cost.scan_launch(5000, 4096)
    check(c["ops"] == 4096 * 5000 * 48 and c["bytes"] > 5000 * 40,
          "scan cost grows as steps x nodes")


def check_checker() -> None:
    """The `correct` comparison rejects doctored allocation lists."""
    from benchmark.loader import load_json, load_module

    cfg = dict(load_json("configs", "csi50k"), nodes=50, count_per_job=4)
    mod = load_module("configs", "csi50k")
    nodes, fleet = mod.build_fleet(cfg, 3)
    jobs = [mod.make_job(cfg, i) for i in range(10)]
    ids = [n.id for n in nodes]
    zones = cfg["zones"]
    good = {j["ID"]: [ids[(i % zones) + zones * k] for k in range(4)]
            for i, j in enumerate(jobs)}
    check(mod.check(cfg, fleet, jobs, good) == [], "a sound list passes")
    wrong_zone = {**good, jobs[0]["ID"]: good[jobs[1]["ID"]]}
    check(any("zone" in f for f in mod.check(cfg, fleet, jobs, wrong_zone)),
          "an alloc outside its volume's zone is caught")
    short = {**good, jobs[2]["ID"]: good[jobs[2]["ID"]][:3]}
    check(any("committed != asked" in f
              for f in mod.check(cfg, fleet, jobs, short)),
          "a short count is caught")
    big = [dict(j) for j in jobs]
    crowd = {j["ID"]: [ids[i % zones]] * 4 for i, j in enumerate(jobs)}
    for j in big:
        j["TaskGroups"] = [dict(j["TaskGroups"][0])]
        tg = j["TaskGroups"][0]
        tg["Tasks"] = [dict(tg["Tasks"][0], Resources=dict(
            tg["Tasks"][0]["Resources"], CPU=2000))]
    check(any("over resources" in f
              for f in mod.check(cfg, fleet, big, crowd)),
          "a node over resources - reserved is caught")
    stray = {**good, "nobody-registered-this": [ids[0]]}
    check(any("nobody" in f for f in mod.check(cfg, fleet, jobs, stray)),
          "allocations of an unregistered job are caught")

    scfg = dict(load_json("configs", "spread5k"), nodes=60,
                count_per_job=100)
    smod = load_module("configs", "spread5k")
    snodes, sfleet = smod.build_fleet(scfg, 3)
    by_dc = {dc: [n.id for n in snodes if n.datacenter == dc]
             for dc in ("dc1", "dc2", "dc3")}
    sjob = smod.make_job(scfg, 0)

    def split(a, b, c):
        return {sjob["ID"]: [by_dc["dc1"][i % 20] for i in range(a)]
                + [by_dc["dc2"][i % 20] for i in range(b)]
                + [by_dc["dc3"][i % 20] for i in range(c)]}

    ref = smod.reference_shares(scfg, 1)[0]
    near = [round(v) for v in ref.values()]
    near[0] += 100 - sum(near)
    check(abs(sum(ref.values()) - 100.0) < 1e-9 and ref["dc1"] > ref["dc2"]
          > ref["dc3"] > 5, f"the plain reference spreads a job towards "
          f"its targets ({near} of 100 on 60 nodes)")
    check(smod.check(scfg, sfleet, [sjob], split(*near)) == []
          and smod.check(scfg, sfleet, [sjob], split(
              near[0] + 1, near[1], near[2] - 1)) == [],
          "a spread on or a point off the reference's passes")
    check(any("spread" in f for f in
              smod.check(scfg, sfleet, [sjob], split(34, 33, 33))),
          "an even split is caught")
    check(any("spread" in f for f in
              smod.check(scfg, sfleet, [sjob], split(100, 0, 0))),
          "a job all in one datacenter (an ignored stanza) is caught")


def check_files() -> None:
    """Every file BENCHMARK.json names exists, names and units use the
    allowed characters, and the data files agree with it."""
    from benchmark.loader import load_json, load_module

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    check(set(bench) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"},
          "BENCHMARK.json has exactly the contract's keys")
    names = ([c["name"] for c in bench["configs"]]
             + [w["name"] for w in bench["workloads"]]
             + [w["traffic"] for w in bench["workloads"]]
             + [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
             + [k for c in bench["configs"] for k in c["reduced"]])
    check(all(NAME.match(n) for n in names), "names in the allowed characters")
    check(all(UNIT.match(m["unit"])
              for m in bench["end_to_end"] + bench["per_layer"]),
          "units in the allowed characters")
    check(all(len(s) <= 200 and "\n" not in s and "\t" not in s
              for s in [c["source"] for c in bench["configs"]]
              + [c["why"] for c in bench["configs"]]
              + [w["why"] for w in bench["workloads"]]
              + bench["command"]), "sources, whys and command within 200")
    for c in bench["configs"]:
        check(os.path.isfile(os.path.join(ROOT, c["file"])),
              f"{c['file']} exists")
        check(load_json("configs", c["name"])["reduced"] == c["reduced"]
              and load_json("configs", c["name"])["source"] == c["source"],
              f"config {c['name']}: file and BENCHMARK.json agree")
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = {w["name"]: w for w in bench["workloads"]}
    check(sum(w["chips"] == 4 for w in cells.values()) <= max(
        1, len(cells) // 2), "at most half the cells ask for four chips")
    for name, w in cells.items():
        cell = load_json("workloads", name)
        check(all(cell[k] == w[k] for k in ("config", "traffic", "chips",
                                            "why")),
              f"cell {name}: file and BENCHMARK.json agree")
        load_json("traffic", w["traffic"])
        check("setup_s" in cell["end_to_end"] and len(cell["end_to_end"])
              >= 2 and cell["per_layer"], f"cell {name}: set-up, another "
              "end-to-end metric and a per-layer metric")
        for m in cell["end_to_end"]:
            listed = e2e[m].get("workloads")
            check(listed is None or name in listed,
                  f"cell {name} is listed under {m}")
    for m in bench["per_layer"]:
        for c in m.get("workloads", cells):
            reader = load_json("workloads", c).get("readers", {}).get(
                m["name"], m["name"])
            mod = load_module("layer_metrics", reader)
            check(mod.UNIT == m["unit"] and callable(mod.read),
                  f"per-layer {m['name']} in {c}: reader {reader}, in its "
                  "unit")
        moved = e2e[m["moves"]].get("workloads", list(cells))
        check(all(c in moved and m["name"] in
                  load_json("workloads", c)["per_layer"]
                  for c in m.get("workloads", cells)),
              f"per-layer {m['name']}: reported only where {m['moves']} is")
    listed = {m["name"]: m.get("workloads", list(cells))
              for m in bench["per_layer"]}
    for name in cells:
        check(all(name in listed.get(m, ())
                  for m in load_json("workloads", name)["per_layer"]),
              f"cell {name}: every per-layer metric of its file is listed "
              "for it in BENCHMARK.json")


def rehearse_one(cell_name: str) -> int:
    from benchmark import run
    from benchmark.loader import load_json

    cell = load_json("workloads", cell_name)
    config = dict(load_json("configs", cell["config"])["rehearse"])
    traffic = dict(load_json("traffic", cell["traffic"])["rehearse"])
    return run.run_cell(cell_name, 0, traffic.pop("seconds"), False,
                        platform="cpu",
                        overrides={"config": config, "traffic": traffic})


def rehearse_all() -> None:
    # every cell that has a file, BENCHMARK.json's and the parked ones
    cells = sorted(f[:-5] for f in os.listdir(
        os.path.join(os.path.dirname(HERE), "workloads"))
        if f.endswith(".json"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for name in cells:
        p = subprocess.run(
            [sys.executable, "-m", "benchmark.selftest", "rehearse-one",
             name], cwd=ROOT, env=env, capture_output=True, text=True,
            timeout=900)
        last = (p.stdout.strip().splitlines() or [""])[-1]
        try:
            out = json.loads(last)
        except ValueError:
            out = {}
        ok = (p.returncode == 0 and out.get("correct") is True
              and out.get("failed") == 0
              and out.get("device", {}).get("platform") == "cpu")
        check(ok, f"rehearsal of {name} on platform cpu: correct, 0 failed "
              f"of {out.get('attempted')}")
        if not ok:
            print(p.stdout[-2000:], p.stderr[-2000:], sep="\n")


def main(argv) -> int:
    if argv[:1] == ["rehearse-one"]:
        return rehearse_one(argv[1])
    check_stats()
    check_schedule()
    check_trace()
    check_cost_and_peaks()
    check_checker()
    check_files()
    if argv[:1] == ["rehearse"]:
        rehearse_all()
    print(f"{len(failures)} failed")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
