"""The flat multi-eval kernel's share of its roofline on waves that hold
spread items, fresh and chained launches together: least time for one
launch of the window's mean wave (benchmark/spread_cost.py: one pass
over every node a PLACEMENT of an evaluation with a stanza, a pass a
round bucket for one without, by the configuration's job mix; padding
rounds not counted) over its measured device time.  The bound is printed
on an earlier line.  A trace without the kernel, or a configuration
without a job mix, reads nothing."""

UNIT = "%"
PROGRAMS = ("jit_place_multi_packed", "jit_place_multi_chained")


def read(run):
    from benchmark import kernel_cost, peaks, spread_cost, system_cost
    progs = [v for n, v in (run.trace.get("programs") or {}).items()
             if n.startswith(PROGRAMS)]
    launches = sum(c for c, _ in progs)
    items = [w["items"] for w in run.tap_window["waves"] if "items" in w]
    mix = run.cfg.get("job_mix") or ()
    if not launches or not items or not any("spread_weight" in m
                                            for m in mix):
        return None
    measured = sum(s for _, s in progs) / launches
    rounds = spread_cost.rounds_per_wave(mix, sum(items) / len(items))
    # one static signature: no job constrains but on the mock job's
    # kernel.name
    _, terms = system_cost.job_shape(run.jobs[0])
    cost = spread_cost.spread_launch(run.cfg["nodes"], rounds, terms)
    r = kernel_cost.roofline(cost, peaks.peaks_for(run.device["kind"]),
                             measured)
    print(f"place_multi_spread_roofline: {r['bound']}-bound, least "
          f"{r['least_s'] * 1e6:.2f} us a launch of {rounds:.1f} real "
          f"rounds over {run.cfg['nodes']} nodes ({cost['bytes']:.0f} "
          f"bytes, {cost['ops']:.0f} ops), measured {measured * 1e3:.3f} "
          f"ms over {launches} launches", flush=True)
    return r["share_pct"]
