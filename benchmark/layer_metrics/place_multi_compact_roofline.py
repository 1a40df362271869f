"""The compact laned multi-eval kernel's share of its roofline, fresh and
chained launches together: least time for one launch of the window's
mean wave (benchmark/kernel_cost.compact_launch: one water-fill round
per evaluation of count <= 512) over its measured device time."""

UNIT = "%"


def read(run):
    from benchmark import kernel_cost, peaks
    progs = [v for n, v in (run.trace.get("programs") or {}).items()
             if n.startswith("jit_place_multi_compact")]
    launches = sum(c for c, _ in progs)
    items = [w["items"] for w in run.tap_window["waves"] if "items" in w]
    if not launches or not items:
        return None
    measured = sum(s for _, s in progs) / launches
    rounds = sum(items) / len(items) * -(-run.cfg["count_per_job"] // 512)
    cost = kernel_cost.compact_launch(run.cfg["nodes"], run.cfg["zones"],
                                      rounds)
    r = kernel_cost.roofline(cost, peaks.peaks_for(run.device["kind"]),
                             measured)
    print(f"place_multi_compact_roofline: {r['bound']}-bound, least "
          f"{r['least_s'] * 1e6:.2f} us a launch of {rounds:.1f} rounds, "
          f"measured {measured * 1e3:.3f} ms over {launches} launches",
          flush=True)
    return r["share_pct"]
