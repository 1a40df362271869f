"""The flat multi-eval kernel's share of its roofline on waves that hold
static port asks, fresh and chained launches together: least time for one
launch of the window's mean wave (benchmark/ports_cost.py: a pass over
every node a job of the configuration's mix, the port state's few
operations on the rounds that ask a static port, a holder bit a node and
value read and written once; padding rounds not counted) over its
measured device time.  The bound is printed on an earlier line.  A trace
without the kernel, or a configuration without static ports, reads
nothing."""

UNIT = "%"
PROGRAMS = ("jit_place_multi_packed", "jit_place_multi_chained")


def read(run):
    from benchmark import kernel_cost, peaks, ports_cost, system_cost
    progs = [v for n, v in (run.trace.get("programs") or {}).items()
             if n.startswith(PROGRAMS)]
    launches = sum(c for c, _ in progs)
    items = [w["items"] for w in run.tap_window["waves"] if "items" in w]
    mix = run.cfg.get("job_mix") or ()
    values = run.cfg.get("static_ports") or ()
    if not launches or not items or not values:
        return None
    measured = sum(s for _, s in progs) / launches
    rounds = ports_cost.rounds_per_wave(mix, sum(items) / len(items))
    # one static signature: no job constrains but on the mock job's
    # kernel.name (distinct_hosts is no static term)
    _, terms = system_cost.job_shape(run.jobs[0])
    cost = ports_cost.ports_launch(
        run.cfg["nodes"], rounds, ports_cost.static_rounds_share(mix),
        len(values), terms)
    r = kernel_cost.roofline(cost, peaks.peaks_for(run.device["kind"]),
                             measured)
    print(f"place_multi_ports_roofline: {r['bound']}-bound, least "
          f"{r['least_s'] * 1e6:.2f} us a launch of {rounds:.1f} real "
          f"rounds over {run.cfg['nodes']} nodes with {len(values)} static "
          f"port values ({cost['bytes']:.0f} bytes, {cost['ops']:.0f} ops), "
          f"measured {measured * 1e3:.3f} ms over {launches} launches",
          flush=True)
    return r["share_pct"]
