"""Of the seconds the chips used spent in XLA ops (containers left out),
the share in collectives: ops whose HLO kind is all-gather, all-reduce,
reduce-scatter, collective-permute or all-to-all, their `-start` /
`-done` halves included.  Read from the run's own `.xplane.pb` through
trace_reduce.reduce_trace, one more pass over the file than the run's
reduction makes (the seconds it took are printed).  Each chip's op
seconds are printed too, so a straggling shard is seen.  The engine's
own figure for the same launches is mesh4.collective_kb_per_wave."""

import os
import time

from benchmark import trace_reduce as tr

UNIT = "%"
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter",
               "collective-permute", "all-to-all")


def is_collective(op_event_name: str) -> bool:
    """By the HLO op kind, the second word of trace_reduce.op_name's
    `%all-gather.3 all-gather`; an op with no kind in its name is none."""
    _, _, kind = tr.op_name(op_event_name).partition(" ")
    return kind.startswith(COLLECTIVES)


def share(reduced: dict, n_chips: int):
    """(share in percent, [(chip, op seconds, collective seconds)]) over
    the first `n_chips` device planes; None where no op was read."""
    per_chip = []
    for c in sorted(reduced["chips"])[:n_chips]:
        ops = reduced["chips"][c]["ops"]
        per_chip.append((c, sum(ops.values()),
                         sum(s for n, s in ops.items() if is_collective(n))))
    total = sum(t for _, t, _ in per_chip)
    if not reduced.get("ops_read") or not total:
        return None
    return 100.0 * sum(k for _, _, k in per_chip) / total, per_chip


def read(run):
    if not run.trace:
        return None
    path = tr.find_xplane(os.path.join(run.tmp, "trace"))
    if path is None:
        return None
    t0 = time.monotonic()
    got = share(tr.reduce_trace(path), run.cell["chips"])
    if got is None:
        return None
    pct, per_chip = got
    print("mesh4.collective_share: op seconds (collective seconds) by chip "
          + ", ".join(f"{c}: {t:.4f} ({k:.4f})" for c, t, k in per_chip)
          + f"; read in {time.monotonic() - t0:.1f} s", flush=True)
    return pct
