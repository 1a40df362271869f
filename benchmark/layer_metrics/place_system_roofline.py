"""`place_system`'s share of its roofline: least time for one launch
(benchmark/system_cost.system_launch at the fleet's size and the job's
shape, against the peaks of benchmark/peaks.py) over its measured device
time.  The bound is printed on an earlier line.  A program without the
kernel (any commit before it) reads nothing."""

UNIT = "%"


def read(run):
    from benchmark import kernel_cost, peaks, system_cost
    hit = (run.trace.get("programs") or {}).get("jit_place_system")
    if not hit or not hit[0]:
        return None
    groups, terms = system_cost.job_shape(run.jobs[0])
    cost = system_cost.system_launch(run.cfg["nodes"], groups, terms)
    r = kernel_cost.roofline(cost, peaks.peaks_for(run.device["kind"]),
                             hit[1] / hit[0])
    print(f"place_system_roofline: {r['bound']}-bound, least "
          f"{r['least_s'] * 1e6:.2f} us a launch ({cost['bytes']:.0f} bytes, "
          f"{cost['ops']:.0f} ops), measured {hit[1] / hit[0] * 1e3:.3f} ms "
          f"over {hit[0]} launches", flush=True)
    return r["share_pct"]
