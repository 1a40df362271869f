"""The flat multi-eval kernel's share of its roofline on waves of jobs of
several task groups, fresh and chained launches together: least time for
one launch of the window's mean wave (benchmark/groups_cost.py: a pass
over every node a group of the configuration's mix, evals a wave from
the wave records' `items`; padding rounds not counted) over its measured
device time.  The bound is printed on an earlier line.  A trace without
the kernel, or a configuration whose mix names no group counts, reads
nothing."""

UNIT = "%"
PROGRAMS = ("jit_place_multi_packed", "jit_place_multi_chained")


def read(run):
    from benchmark import groups_cost, kernel_cost, peaks, system_cost
    progs = [v for n, v in (run.trace.get("programs") or {}).items()
             if n.startswith(PROGRAMS)]
    launches = sum(c for c, _ in progs)
    items = [w["items"] for w in run.tap_window["waves"] if "items" in w]
    mix = run.cfg.get("job_mix") or ()
    if not launches or not items or not all("counts" in m for m in mix):
        return None
    measured = sum(s for _, s in progs) / launches
    rounds = groups_cost.rounds_per_wave(mix, sum(items) / len(items))
    # one static signature: every group carries the mock job's
    # kernel.name constraint and nothing else
    _, terms = system_cost.job_shape(run.jobs[0])
    cost = groups_cost.groups_launch(run.cfg["nodes"], rounds, terms)
    r = kernel_cost.roofline(cost, peaks.peaks_for(run.device["kind"]),
                             measured)
    print(f"place_multi_groups_roofline: {r['bound']}-bound, least "
          f"{r['least_s'] * 1e6:.2f} us a launch of {rounds:.1f} real "
          f"rounds (a group each) over {run.cfg['nodes']} nodes "
          f"({cost['bytes']:.0f} bytes, {cost['ops']:.0f} ops), measured "
          f"{measured * 1e3:.3f} ms over {launches} launches", flush=True)
    return r["share_pct"]
