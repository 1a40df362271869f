"""The flat multi-eval kernel's share of its roofline on waves that carry
device group slots, fresh and chained launches together: least time for
one launch of the window's mean wave (benchmark/mig_cost.py: a pass over
every node a round, one round an eval of the configuration's count,
slot compares and the charge's select, the slot state and the accept
tables; evals a wave from the wave records' `items`, padding not
counted) over its measured device time.  The bound is printed on an
earlier line.  A trace without the kernel reads nothing."""

UNIT = "%"
PROGRAMS = ("jit_place_multi_packed", "jit_place_multi_chained")


def read(run):
    from benchmark import kernel_cost, mig_cost, multi_cost, peaks
    from benchmark import system_cost
    progs = [v for n, v in (run.trace.get("programs") or {}).items()
             if n.startswith(PROGRAMS)]
    launches = sum(c for c, _ in progs)
    items = [w["items"] for w in run.tap_window["waves"] if "items" in w]
    if not launches or not items or "gpu_shapes" not in run.cfg:
        return None
    measured = sum(s for _, s in progs) / launches
    rounds = (sum(items) / len(items)
              * multi_cost.rounds_per_eval(run.cfg["count_per_job"]))
    slots = mig_cost.slots_of(run.cfg)
    signatures = mig_cost.signatures_of(run.cfg)
    _, terms = system_cost.job_shape(run.jobs[0])
    cost = mig_cost.mig_launch(run.cfg["nodes"], rounds, signatures, terms,
                               slots)
    r = kernel_cost.roofline(cost, peaks.peaks_for(run.device["kind"]),
                             measured)
    print(f"place_multi_mig_roofline: {r['bound']}-bound, least "
          f"{r['least_s'] * 1e6:.2f} us a launch of {rounds:.1f} rounds "
          f"over {run.cfg['nodes']} nodes of {slots} device group slots, "
          f"{signatures} device requests ({cost['bytes']:.0f} bytes, "
          f"{cost['ops']:.0f} ops), measured {measured * 1e3:.3f} ms over "
          f"{launches} launches", flush=True)
    return r["share_pct"]
