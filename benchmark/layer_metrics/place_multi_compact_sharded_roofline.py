"""The node-sharded compact laned kernel's share of its roofline, fresh
and chained launches together: the least time ONE CHIP needs for its
share of one launch of the window's mean wave over the mean device time
of a chip's execution.  The work is the one-chip reader's
(benchmark/kernel_cost.compact_launch, whatever implements it), its
operations and bytes divided by the cell's chips: a sharded launch that
took a chip as long as the whole launch takes one chip reads a quarter
of place_multi_compact_roofline.  The trace's launches are summed over
the chips used, one execution a chip a wave."""

UNIT = "%"


def read(run):
    from benchmark import kernel_cost, peaks
    progs = [v for n, v in (run.trace.get("programs") or {}).items()
             if n.startswith("jit_place_multi_compact_sharded")]
    launches = sum(c for c, _ in progs)
    items = [w["items"] for w in run.tap_window["waves"] if "items" in w]
    if not launches or not items:
        return None
    chips = run.cell["chips"]
    measured = sum(s for _, s in progs) / launches
    rounds = sum(items) / len(items) * -(-run.cfg["count_per_job"] // 512)
    whole = kernel_cost.compact_launch(run.cfg["nodes"], run.cfg["zones"],
                                       rounds)
    cost = {k: v / chips for k, v in whole.items()}
    r = kernel_cost.roofline(cost, peaks.peaks_for(run.device["kind"]),
                             measured)
    print(f"place_multi_compact_sharded_roofline: {r['bound']}-bound, "
          f"least {r['least_s'] * 1e6:.2f} us a chip's execution of "
          f"{rounds:.1f} rounds over {chips} chips, measured "
          f"{measured * 1e3:.3f} ms over {launches} executions "
          f"({launches / chips:.1f} launches)", flush=True)
    return r["share_pct"]
