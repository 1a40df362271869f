"""The flat multi-eval kernel's share of its roofline, fresh and chained
launches together: least time for one launch of the window's mean wave
(benchmark/multi_cost.flat_launch: one water-fill round over every node
per evaluation of the cell's count) over its measured device time.  The
bound is printed on an earlier line.  A trace without the kernel (a cell
whose waves are laned, a program that runs these evals alone) reads
nothing."""

UNIT = "%"
PROGRAMS = ("jit_place_multi_packed", "jit_place_multi_chained")


def read(run):
    from benchmark import kernel_cost, multi_cost, peaks, system_cost
    progs = [v for n, v in (run.trace.get("programs") or {}).items()
             if n.startswith(PROGRAMS)]
    launches = sum(c for c, _ in progs)
    items = [w["items"] for w in run.tap_window["waves"] if "items" in w]
    if not launches or not items:
        return None
    measured = sum(s for _, s in progs) / launches
    rounds = (sum(items) / len(items)
              * multi_cost.rounds_per_eval(run.cfg["count_per_job"]))
    # the signatures a wave holds: the distinct device requests of the
    # configuration's job mix (one request a job here)
    signatures = len({m["device"] for m in run.cfg.get("job_mix", ())}) or 1
    _, terms = system_cost.job_shape(run.jobs[0])
    cost = multi_cost.flat_launch(run.cfg["nodes"], rounds, signatures,
                                  terms)
    r = kernel_cost.roofline(cost, peaks.peaks_for(run.device["kind"]),
                             measured)
    print(f"place_multi_roofline: {r['bound']}-bound, least "
          f"{r['least_s'] * 1e6:.2f} us a launch of {rounds:.1f} rounds "
          f"over {run.cfg['nodes']} nodes ({cost['bytes']:.0f} bytes, "
          f"{cost['ops']:.0f} ops), measured {measured * 1e3:.3f} ms over "
          f"{launches} launches", flush=True)
    return r["share_pct"]
