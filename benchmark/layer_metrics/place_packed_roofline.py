"""The exact scan's share of its roofline: least time for one launch
(benchmark/kernel_cost.scan_launch at the padded step count, against the
peaks of benchmark/peaks.py) over its measured device time.  The bound
is printed on an earlier line."""

UNIT = "%"


def read(run):
    from benchmark import kernel_cost, peaks
    hit = (run.trace.get("programs") or {}).get("jit_place_packed")
    if not hit or not hit[0]:
        return None
    steps = 8
    while steps < run.cfg["count_per_job"]:
        steps *= 2                      # the engine pads steps to a power of 2
    cost = kernel_cost.scan_launch(run.cfg["nodes"], steps)
    r = kernel_cost.roofline(cost, peaks.peaks_for(run.device["kind"]),
                             hit[1] / hit[0])
    print(f"place_packed_roofline: {r['bound']}-bound, least "
          f"{r['least_s'] * 1e6:.2f} us a launch, measured "
          f"{hit[1] / hit[0] * 1e3:.3f} ms over {hit[0]} launches", flush=True)
    return r["share_pct"]
