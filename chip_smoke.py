#!/usr/bin/env python3
"""Chip smoke: the served scheduling path, once, on the accelerator.

    python chip_smoke.py                  # TPU only; fails anywhere else
    python chip_smoke.py --mesh-legs      # multi-chip host: mesh auto vs off
    python chip_smoke.py --scan-fused     # spread5k's eval: XLA vs fused scan
    python chip_smoke.py --cpu-dry-run    # tiny size on the CPU backend

Drives `job register` on the HTTP API -> broker -> worker -> WavePipeline
-> DeviceExecutor -> PlacementEngine kernels -> D2H -> materialize -> plan
queue -> applier commit -> allocations read back over HTTP, at BASELINE
config 5's full size (50,000 nodes x 5 CSI zones x 3 DCs, 384 batch jobs
x 260 placements, plus one spread+affinity service job, then ten more
batch jobs against the loaded fleet), then checks the committed
allocations with plain host code that shares nothing with the kernels,
and checks from the executor's own counters that the device path did
the work.  One process: it holds the chip, and the HTTP client runs in
it.

Scheduling is held while the jobs register (Server.stop_scheduling /
start_scheduling, as bench.py's drain does) and the id pool is seeded,
so wave composition, kernel shapes and the per-eval tie-break seeds
(crc32 of the eval id) are the same in every run from one --seed: a
second run can then be shown to hit the compile cache, and the mesh-on
and mesh-off legs can be compared node for node.

Stdout ends with two JSON lines: the full report (sizes, counters,
compile cache, smoke observations), then the verdict, exactly
{"ok": ..., "device": {"platform", "kind", "count"}}.  Exit code 0 iff
every phase passed.  Wall times in the report are smoke observations,
not measurements.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import time
from collections import Counter

# config 5 of BASELINE.json, as bench.run_config_5 sizes it
FULL = {"nodes": 50_000, "jobs": 384, "per_job": 260, "spread_count": 30}
# --cpu-dry-run: same shape, small enough for the tier-1 CPU suite
TINY = {"nodes": 600, "jobs": 200, "per_job": 6, "spread_count": 30}
ZONES, DCS = 5, 3
BATCH_ASK = (10, 10)               # cpu MHz, memory MB per placement
SCHEDULE_DEADLINE_S = 900.0
CLIENT_TERMINAL = ("complete", "failed", "lost")


def build_fleet(n_nodes: int, seed: int):
    """The config-5 fleet (bench._build_bench_cluster's shape) with ids
    and capacities drawn from `seed`: node i sits in dc{1 + i % 3} and
    storage zone{i % 5}, and runs the CSI node plugin."""
    from nomad_tpu import mock

    rng = random.Random(seed)
    ids = seeded_ids(rng, n_nodes)
    nodes = []
    for i in range(n_nodes):
        n = mock.node()
        n.id = ids[i]
        n.name = f"smoke-node-{i}"
        n.datacenter = f"dc{1 + i % DCS}"
        n.attributes["platform.rack"] = f"r{i % 20}"
        n.attributes["storage.topology"] = f"zone{i % ZONES}"
        n.csi_node_plugins["ebs0"] = True
        n.resources.cpu = rng.choice([4000, 8000, 16000])
        n.resources.memory_mb = rng.choice([8192, 16384, 32768])
        nodes.append(n)
    return nodes


def batch_job(i: int, per_job: int):
    """run_config_5's make_job: zone-pinned by a read-only CSI claim."""
    from nomad_tpu import mock
    from nomad_tpu.structs import VolumeRequest

    job = mock.batch_job()
    job.id = f"smoke-batch-{i:04d}"
    job.datacenters = [f"dc{d + 1}" for d in range(DCS)]
    tg = job.task_groups[0]
    tg.count = per_job
    tg.tasks[0].resources.cpu, tg.tasks[0].resources.memory_mb = BATCH_ASK
    tg.volumes = {"data": VolumeRequest(
        name="data", type="csi", source=f"vol-zone{i % ZONES}",
        read_only=True)}
    return job


def spread_job(count: int):
    """BASELINE config 3's shape: spread over the DCs + an affinity, so
    the exact per-placement scan compiles at the full node count too."""
    from nomad_tpu import mock

    job = mock.spread_job()
    job.id = "smoke-spread"
    job.task_groups[0].count = count
    return job


# --scan-fused: spread5k's eval (benchmark/configs/spread5k.json) through
# both single-device scans; the dry run is a tiny one, the kernel in
# Pallas' interpreter
SCAN_FULL = {"nodes": 5_000, "steps": 3_000, "p_pad": 4_096}
SCAN_TINY = {"nodes": 300, "steps": 30, "p_pad": 64}
SCAN_LAUNCHES = 5


def scan_leg_inputs(sizes: dict, seed: int):
    """One spread5k eval's scan inputs, lowered as the engine lowers a
    solo eval: `steps` active placements of the job's group, padded to
    `p_pad`, on the empty fleet, the tie-break seed live."""
    import jax.numpy as jnp
    import numpy as np

    from benchmark.configs import spread5k
    from nomad_tpu.ops.select import PlacementInputs
    from nomad_tpu.pack import ClusterPacker, lower_spreads
    from nomad_tpu.scheduler import Harness
    from nomad_tpu.structs import Job, codec

    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "benchmark", "configs", "spread5k.json")) as f:
        cfg = dict(json.load(f), nodes=sizes["nodes"],
                   count_per_job=sizes["steps"])
    nodes, _ = spread5k.build_fleet(cfg, seed)
    job = codec.decode(Job, spread5k.make_job(cfg, 0))
    h = Harness()
    h.state.upsert_nodes(nodes)
    h.state.upsert_job(job)
    snap = h.snapshot()
    packer = ClusterPacker()
    t = packer.build(snap)
    tgs = job.task_groups
    tgt = packer.lower_task_groups(job, tgs)
    ctx = packer.job_context(job, snap, t)
    sp = lower_spreads(packer, job, t, snap)
    pd = packer.lower_distinct(job, tgs, tgt, t, snap)
    p_pad = sizes["p_pad"]
    return PlacementInputs(
        attrs=jnp.asarray(t.attrs), cap=jnp.asarray(t.cap),
        used0=jnp.asarray(t.used), elig=jnp.asarray(t.elig.astype(bool)),
        dc_mask=jnp.asarray(ctx.dc_mask),
        pool_mask=jnp.asarray(ctx.pool_mask), luts=jnp.asarray(tgt.luts),
        con=jnp.asarray(tgt.con), aff=jnp.asarray(tgt.aff),
        req=jnp.asarray(tgt.req),
        desired=jnp.asarray(np.array([tg.count for tg in tgs], np.int32)),
        dh_limit=jnp.asarray(tgt.dh_limit),
        sp_nodeval=jnp.asarray(sp.sp_nodeval),
        sp_weight=jnp.asarray(sp.sp_weight),
        sp_expected=jnp.asarray(sp.sp_expected),
        sp_counts0=jnp.asarray(sp.sp_counts0),
        pd_nodeval=jnp.asarray(pd.pd_nodeval),
        pd_limit=jnp.asarray(pd.pd_limit), pd_apply=jnp.asarray(pd.pd_apply),
        pd_counts0=jnp.asarray(pd.pd_counts0),
        tg_idx=jnp.zeros(p_pad, jnp.int32),
        prev_row=jnp.full(p_pad, -1, jnp.int32),
        active=jnp.asarray(np.arange(p_pad) < sizes["steps"]),
        job_count0=jnp.asarray(ctx.job_count),
        spread_algo=jnp.asarray(False),
        seed=jnp.asarray(seed & 0xFFFFFFFF, jnp.uint32))


def run_scan_leg(sizes: dict, seed: int, interpret: bool) -> dict:
    """Both single-device scans on one eval's inputs: the rows they
    agree on and differ in (for a differing pick, the two picks' score
    gap in ulps), and each one's ms a launch, on the device's clock from
    a profiler trace and on the host's around a blocked launch."""
    import tempfile

    import jax
    import numpy as np

    from benchmark.trace_reduce import find_xplane, reduce_trace
    from nomad_tpu.ops import scan_fused, select

    def place_packed_fused(inp):
        return scan_fused.place_packed_fused(inp, interpret=interpret)

    inp = scan_leg_inputs(sizes, seed)
    # programs `jit_place_packed_xla` and `jit_place_packed_fused`
    fns = {"xla": jax.jit(select.place_packed_xla),
           "fused": jax.jit(place_packed_fused)}
    outs = {k: [np.asarray(x) for x in f(inp)] for k, f in fns.items()}
    host_ms = {}
    with tempfile.TemporaryDirectory() as trace_dir:
        with jax.profiler.trace(trace_dir):
            for k, f in fns.items():
                t0 = time.perf_counter()
                for _ in range(SCAN_LAUNCHES):
                    jax.block_until_ready(f(inp))
                host_ms[k] = (time.perf_counter() - t0) * 1e3 / SCAN_LAUNCHES
        path = find_xplane(trace_dir)
        chips = reduce_trace(path, with_ops=False)["chips"] if path else {}
    device_ms = {}
    for chip in chips.values():
        for name, (launches, secs) in chip["programs"].items():
            for k in fns:
                if name.startswith("jit_") and name.endswith(k) and launches:
                    device_ms[k] = secs * 1e3 / launches
    (xbuf, xused, xjc), (fbuf, fused_used, fjc) = outs["xla"], outs["fused"]
    differ = np.flatnonzero((xbuf != fbuf).any(axis=1))
    pick_differ = np.flatnonzero(xbuf[:, 0] != fbuf[:, 0])
    gaps = []
    for i in pick_differ[:8].tolist():
        a, b = xbuf[i, 1].astype(np.int64), fbuf[i, 1].astype(np.int64)
        gaps.append({"row": i, "picks": [int(xbuf[i, 0]), int(fbuf[i, 0])],
                     "score_gap_ulps": int(abs(a - b))})
    # each column's differing rows, and its widest gap in ulps where it
    # holds a float's bits (the reported scores: 1 and 5-7)
    columns = {}
    for c in np.flatnonzero((xbuf != fbuf).any(axis=0)).tolist():
        rows = xbuf[:, c] != fbuf[:, c]
        gap = np.abs(xbuf[rows, c].astype(np.int64)
                     - fbuf[rows, c].astype(np.int64))
        columns[str(c)] = {"rows": int(rows.sum()),
                           "max_gap": int(gap.max())}
    steps = sizes["steps"]
    return {
        "sizes": sizes,
        "rows_equal": int(len(xbuf) - len(differ)),
        "rows_differing": int(len(differ)),
        "picks_differing": int(len(pick_differ)),
        "first_differing_picks": gaps,
        "differing_columns": columns,
        "final_state_equal": bool((xused == fused_used).all()
                                  and (xjc == fjc).all()),
        "placed": int((fbuf[:steps, 0] >= 0).sum()),
        "device_ms_per_launch": device_ms,
        "device_us_per_step": {k: v * 1e3 / steps
                               for k, v in device_ms.items()},
        "host_ms_per_blocked_launch": host_ms,
    }


def cache_entries(path: str) -> int:
    """Executables in the persistent compile cache (access-time
    sidecars are not entries)."""
    if not os.path.isdir(path):
        return 0
    return sum(1 for f in os.listdir(path) if not f.endswith("-atime"))


def seeded_ids(rng: random.Random, count: int) -> list:
    """UUIDv4-shaped ids drawn from `rng`."""
    return ["%08x-%04x-4%03x-%04x-%012x" % (
        rng.getrandbits(32), rng.getrandbits(16), rng.getrandbits(12),
        rng.getrandbits(16), rng.getrandbits(48)) for _ in range(count)]


def run_leg(sizes: dict, seed: int, mesh, platform: str) -> dict:
    """One pass of the served path on a fresh Agent.  Returns the leg's
    report; report["failures"] lists every check that did not hold."""
    from nomad_tpu.agent import Agent
    from nomad_tpu.api.client import APIClient
    from nomad_tpu.core.logging import RING
    from nomad_tpu.ops.engine import mesh_launches_by_program
    from nomad_tpu.structs import CSIVolume, codec
    from nomad_tpu.structs import structs as structs_mod

    failures: list = []
    sharded0 = mesh_launches_by_program()     # the counter is the process's
    phase_s: dict = {}
    t_leg = time.time()
    ring_level = RING.min_level

    def check(ok: bool, what: str) -> None:
        if not ok:
            failures.append(what)

    # ---- fleet: agent up, nodes loaded in bulk, volumes over HTTP ----
    t0 = time.perf_counter()
    nodes = build_fleet(sizes["nodes"], seed)
    # log_level="warn": the 2048-record ring then cannot lose an early
    # launch failure under the run's debug/info traffic
    agent = Agent(num_clients=0, heartbeat_ttl=86400.0, log_level="warn",
                  mesh=mesh)
    try:
        agent.start()
        server = agent.server
        api = APIClient(address=agent.address, timeout=120.0)
        server.state.upsert_nodes(nodes)
        zone_nodes = {z: [n.id for n in nodes[z::ZONES]]
                      for z in range(ZONES)}
        for z in range(ZONES):
            vol = CSIVolume(id=f"vol-zone{z}", plugin_id="ebs0",
                            access_mode="multi-node-multi-writer",
                            topology_node_ids=tuple(zone_nodes[z]))
            api.volumes.register(vol.id, vol.plugin_id,
                                 **codec.encode(vol))
        check(len(api.volumes.list()) == ZONES, "volumes registered")
        phase_s["fleet"] = time.perf_counter() - t0

        # ---- rounds: register over HTTP, wait on eval status over HTTP ----
        terminal = ("complete", "failed", "canceled")

        def run_round(round_jobs, id_seed: int) -> Counter:
            """Register `round_jobs` with scheduling held, release it,
            and poll until every eval settles; returns final statuses."""
            t0 = time.perf_counter()
            server.stop_scheduling()
            structs_mod._id_pool[:] = seeded_ids(random.Random(id_seed),
                                                 16 * len(round_jobs))
            eval_ids = [api.jobs.register(codec.encode(j))["EvalID"]
                        for j in round_jobs]
            structs_mod._id_pool.clear()      # random ids from here on
            server.start_scheduling()
            check(all(eval_ids), "every registration returned an eval id")
            phase_s["register"] = (phase_s.get("register", 0.0)
                                   + time.perf_counter() - t0)
            t0 = time.perf_counter()
            want = set(eval_ids)
            deadline = time.monotonic() + SCHEDULE_DEADLINE_S
            while True:
                status = {e["ID"]: e["Status"]
                          for e in api.evaluations.list()
                          if e["ID"] in want}
                if (len(status) == len(want)
                        and all(s in terminal for s in status.values())):
                    break
                if time.monotonic() > deadline:
                    failures.append(
                        f"evals not settled after "
                        f"{SCHEDULE_DEADLINE_S:.0f}s: "
                        f"{dict(Counter(status.values()))}")
                    break
                time.sleep(0.25)
            # the answer a user polling one eval would get, per eval
            final = Counter(api.evaluations.info(eid)["Status"]
                            for eid in eval_ids)
            check(final == Counter({"complete": len(eval_ids)}),
                  f"every eval complete (got {dict(final)})")
            phase_s["schedule"] = (phase_s.get("schedule", 0.0)
                                   + time.perf_counter() - t0)
            return final

        # round 1, the spread job last: six full coupled waves (the
        # first fresh, the rest chained on their predecessor's
        # device-side usage), then the exact scan on its own
        jobs = [batch_job(i, sizes["per_job"])
                for i in range(sizes["jobs"])]
        jobs.append(spread_job(sizes["spread_count"]))
        final = run_round(jobs, seed + 1)
        # round 2, two more batch jobs per zone against the now-loaded
        # fleet: the spread plan was foreign to the chain, so this wave
        # starts from packer-synced usage, which the engine brings up to
        # date by replaying the commit deltas ON the device (the
        # scatter kernel) rather than re-uploading [N, 3]
        followups = [batch_job(sizes["jobs"] + i, sizes["per_job"])
                     for i in range(2 * ZONES)]
        final += run_round(followups, seed + 2)
        jobs += followups
        asked = {j.id: j.task_groups[0].count for j in jobs}

        # ---- readback: allocations over HTTP ----
        t0 = time.perf_counter()
        cols = api.get("/v1/allocations", columnar="true")["Columns"]
        by_job: dict = {}
        for job_id, node_id, cstatus in zip(cols["JobID"], cols["NodeID"],
                                            cols["ClientStatus"]):
            if cstatus not in CLIENT_TERMINAL:
                by_job.setdefault(job_id, []).append(node_id)
        # full wire form for the spread job and one batch job per zone
        sampled = jobs[:ZONES] + [jobs[sizes["jobs"]]]
        for job in sampled:
            rows = [a for a in api.jobs.allocations(job.id)
                    if a["DesiredStatus"] == "run"
                    and a["ClientStatus"] not in CLIENT_TERMINAL]
            ask = job.task_groups[0].tasks[0].resources
            check(sorted(a["NodeID"] for a in rows)
                  == sorted(by_job.get(job.id, [])),
                  f"{job.id}: per-job and columnar reads agree")
            check(all(a["Resources"]["CPU"] == ask.cpu
                      and a["Resources"]["MemoryMB"] == ask.memory_mb
                      for a in rows),
                  f"{job.id}: alloc resources equal the ask")
        phase_s["readback"] = time.perf_counter() - t0

        # ---- check: plain host code over the fleet this script built ----
        t0 = time.perf_counter()
        node_by_id = {n.id: (i, n) for i, n in enumerate(nodes)}
        placed = {j: len(v) for j, v in by_job.items()}
        short = {j: (placed.get(j, 0), c) for j, c in asked.items()
                 if placed.get(j, 0) != c}
        check(not short and set(placed) <= set(asked),
              "committed == asked per job; (placed, asked) off for "
              f"{dict(list(short.items())[:5])}")
        used_cpu: Counter = Counter()
        used_mem: Counter = Counter()
        bad_zone = bad_dc = unknown = 0
        for job in jobs:
            tg = job.task_groups[0]
            ask = tg.tasks[0].resources
            vol = tg.volumes.get("data") if tg.volumes else None
            zone = int(vol.source[len("vol-zone"):]) if vol else None
            for node_id in by_job.get(job.id, ()):
                hit = node_by_id.get(node_id)
                if hit is None:
                    unknown += 1
                    continue
                i, n = hit
                if zone is not None and i % ZONES != zone:
                    bad_zone += 1
                if n.datacenter not in job.datacenters:
                    bad_dc += 1
                used_cpu[node_id] += ask.cpu
                used_mem[node_id] += ask.memory_mb
        check(unknown == 0, f"{unknown} allocs on nodes not in the fleet")
        check(bad_zone == 0, f"{bad_zone} allocs outside their volume zone")
        check(bad_dc == 0, f"{bad_dc} allocs outside their job's DCs")
        over = [nid for nid in used_cpu
                if used_cpu[nid] > (node_by_id[nid][1].resources.cpu
                                    - node_by_id[nid][1].reserved.cpu)
                or used_mem[nid] > (node_by_id[nid][1].resources.memory_mb
                                    - node_by_id[nid][1].reserved.memory_mb)]
        check(not over, f"{len(over)} nodes over capacity")
        # the spread job asks for 50/30/20 % over dc1/dc2/dc3
        spread_dcs = Counter(node_by_id[nid][1].datacenter
                             for nid in by_job.get("smoke-spread", ())
                             if nid in node_by_id)
        off_target = {dc: spread_dcs[dc] for dc, pct in
                      (("dc1", 50), ("dc2", 30), ("dc3", 20))
                      if abs(spread_dcs[dc]
                             - sizes["spread_count"] * pct / 100) > 1}
        check(not off_target, f"spread off its targets: {dict(spread_dcs)}")
        phase_s["check"] = time.perf_counter() - t0

        # ---- device path: the executor's own counters ----
        ex = server.executor
        sites = ex.ledger()["compile"]["sites"]
        npad = server.engine._padded_n(sizes["nodes"])

        def site_at_n(kind: str) -> bool:
            return any(s.startswith(f"engine.{kind}/")
                       and str(npad) in s.split("/")[1].split("x")
                       for s in sites)

        check(ex.stats["dispatches"] >= 2,
              f"dispatches >= 2 (got {ex.stats['dispatches']})")
        check(ex.stats["resident_waves"] >= 1,
              f"resident_waves >= 1 (got {ex.stats['resident_waves']})")
        replayed = ex.upload_bytes_by_cause.get("invalidation-replay", 0)
        check(replayed > 0, "round 2 replayed usage deltas on the device")
        check(site_at_n("multi_compact"),
              f"multi_compact launched at {npad} ({sorted(sites)})")
        check(site_at_n("multi_compact_chained"),
              f"multi_compact_chained launched at {npad} ({sorted(sites)})")
        workers = [dict(w.stats) for w in server.workers]
        nacked = sum(int(w["nacked"]) for w in workers)
        check(nacked == 0, f"worker nacked == 0 (got {nacked})")
        # a failed launch, prefetch or worker pass logs at error
        bad_logs = [r for r in RING.tail(2048, min_level="error")
                    if r["ts"] >= t_leg and r["component"] == "worker"]
        check(not bad_logs, f"worker error log records: {bad_logs[:3]}")
        # where the engine's cached node tensors live (its device cache
        # has no public reader; this is the one private peek)
        tensor_platforms = sorted({d.platform
                                   for arr in server.engine._dev_cache.values()
                                   for d in arr.devices()})
        check(tensor_platforms == [platform],
              f"node tensors on {platform} (got {tensor_platforms})")
        # the node-sharded programs this leg launched, by the name a
        # trace shows them under (parallel/mesh.py PROGRAM_NAMES)
        sharded = {name: n - sharded0.get(name, 0)
                   for name, n in mesh_launches_by_program().items()
                   if n > sharded0.get(name, 0)}
        if server.engine.mesh is not None:
            check(ex.stats["collective_bytes"] > 0,
                  "mesh launches metered collective bytes")
            for name in ("place_multi_compact_sharded",
                         "place_multi_compact_sharded_chained",
                         "place_sharded_packed", "scatter_add_sharded"):
                check(sharded.get(name, 0) > 0,
                      f"{name} launched (got {sharded})")
        else:
            check(not sharded,
                  f"no sharded program launched with the mesh off "
                  f"(got {sharded})")

        return {
            "failures": failures,
            "mesh_devices": (server.engine.n_devices
                             if server.engine.mesh is not None else 0),
            "sharded_programs": sharded,
            "evals": dict(final),
            "placed": sum(placed.values()),
            "asked": sum(asked.values()),
            "spread_by_dc": dict(spread_dcs),
            "nodes_used": len(used_cpu),
            "executor": {
                **{k: int(ex.stats[k]) for k in (
                    "dispatches", "resident_waves", "invalidations",
                    "collective_bytes", "d2h_bytes", "upload_bytes",
                    "hbm_high_watermark_bytes")},
                "upload_bytes_by_cause": dict(ex.upload_bytes_by_cause)},
            "pipeline": [dict(w.pipeline.stats) for w in server.workers],
            "workers": workers,
            "node_tensor_platforms": tensor_platforms,
            "smoke_observations": {
                "phase_wall_s": {k: round(v, 3)
                                 for k, v in phase_s.items()},
                # StageTimers intervals per wave, in wave order; a
                # prefetched wave's "device" interval also spans the
                # previous wave's host phase
                "wave_stage_s": {
                    stage: [round(t1 - t0, 4) for _, t0, t1
                            in server.stage_timers.intervals(stage)]
                    for stage in ("dispatch", "device", "d2h")}},
            "_by_job": {j: sorted(v) for j, v in by_job.items()},
        }
    finally:
        agent.shutdown()
        RING.min_level = ring_level       # the gate is process-wide


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="fleet, capacities and ids are drawn from it")
    ap.add_argument("--cpu-dry-run", action="store_true",
                    help="run a tiny fleet on the CPU backend (never the "
                         "default, never reached by a fallback)")
    ap.add_argument("--mesh-legs", action="store_true",
                    help="multi-device host: run with the node axis "
                         "sharded over every device, then single-device, "
                         "and require identical placements")
    ap.add_argument("--scan-fused", action="store_true",
                    help="spread5k's eval through the XLA scan and the "
                         "fused Pallas scan: rows equal and differing, "
                         "each one's ms a launch")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"jax {jax.__version__} platform={device['platform']} "
          f"device_kind={device['kind']} n_devices={device['count']}",
          flush=True)
    want_platform = "cpu" if args.cpu_dry_run else "tpu"
    if device["platform"] != want_platform:
        print(f"chip_smoke: needs a {want_platform} backend, JAX chose "
              f"{device['platform']!r}"
              + ("" if args.cpu_dry_run else
                 " (--cpu-dry-run is the explicit CPU mode)"),
              file=sys.stderr)
        return 2
    if args.mesh_legs and device["count"] < 2:
        print("chip_smoke: --mesh-legs needs more than one device",
              file=sys.stderr)
        return 2

    import nomad_tpu.ops  # noqa: F401 - places the compile cache

    cache_dir = jax.config.jax_compilation_cache_dir
    cache_before = cache_entries(cache_dir)
    sizes = TINY if args.cpu_dry_run else FULL
    t0 = time.perf_counter()
    if args.scan_fused:
        leg = run_scan_leg(SCAN_TINY if args.cpu_dry_run else SCAN_FULL,
                           args.seed, interpret=args.cpu_dry_run)
        failures = ([] if leg["placed"] == leg["sizes"]["steps"] else
                    [f"scan_fused: {leg['placed']} placed of "
                     f"{leg['sizes']['steps']}"])
        report = {"ok": not failures, "device": device,
                  "jax": jax.__version__, "seed": args.seed,
                  "scan_fused": leg}
        if failures:
            report["failures"] = failures
        print(json.dumps(report), flush=True)
        print(json.dumps({"ok": report["ok"], "device": device}), flush=True)
        return 0 if not failures else 1
    if args.mesh_legs:
        legs = {"mesh_auto": run_leg(sizes, args.seed, None,
                                     want_platform),
                "mesh_off": run_leg(sizes, args.seed, False,
                                    want_platform)}
        a, b = (legs[k].pop("_by_job") for k in ("mesh_auto", "mesh_off"))
        differ = sorted(j for j in set(a) | set(b) if a.get(j) != b.get(j))
        if differ:
            legs["mesh_off"]["failures"].append(
                f"{len(differ)} jobs placed on different node multisets "
                f"with the mesh on and off (first: {differ[:3]})")
        if legs["mesh_auto"]["mesh_devices"] != device["count"]:
            legs["mesh_auto"]["failures"].append("mesh leg ran unsharded")
    else:
        legs = {"served": run_leg(sizes, args.seed, None, want_platform)}
        legs["served"].pop("_by_job")
    failures = [f"{name}: {f}" for name, leg in legs.items()
                for f in leg.pop("failures")]
    # the compile ledger is process-wide and keys a site by kernel kind
    # and shape, not by mesh: with --mesh-legs the first leg's launches
    # are the first launches, the second leg's compiles book as steady
    from nomad_tpu.core.profiling import COMPILE
    first_launch_s = {site: round(v["first_launch_s"], 3) for site, v
                      in sorted(COMPILE.snapshot()["sites"].items())}
    report = {
        "ok": not failures,
        "device": device,
        "jax": jax.__version__,
        "seed": args.seed,
        "sizes": sizes,
        "legs": legs,
        "compile_cache": {"dir": cache_dir, "entries_before": cache_before,
                          "entries_after": cache_entries(cache_dir)},
        "smoke_observations": {
            "total_wall_s": round(time.perf_counter() - t0, 3),
            "first_launch_s": first_launch_s},
    }
    if failures:
        report["failures"] = failures
        for f in failures:
            print(f"FAILED {f}", file=sys.stderr)
    print(json.dumps(report), flush=True)
    # the verdict: these keys and no others, as the last line
    print(json.dumps({"ok": report["ok"], "device": device}), flush=True)
    return 0 if not failures else 1


if __name__ == "__main__":
    sys.exit(main())
