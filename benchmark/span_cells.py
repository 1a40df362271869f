"""One traced run of any cell with every span metric that no cell's file
lists yet: PR 24's (`host_spans.CELLS`) and the ones below.

`run.py` reads a cell's per-layer metrics from the cell's own file, so a
new metric in a cell that exists needs an edit a PR that changes the
program may not make.  Until a `benchmark` PR moves these names into the
cell files and BENCHMARK.json (PERF.md section 7),

    python3 -m benchmark.span_cells --workload <cell> --seed <n> --seconds <s>

merges EXTRA into `host_spans.CELLS` in memory and runs `host_spans.main`,
which appends a cell's names to its per-layer list, in memory too.

EXTRA: per cell, {the metric's name in the cell: its reader file under
benchmark/layer_metrics/}.  A per-layer metric moves ONE end-to-end
metric, so the names are bare in `csi50k-drain` (`placed_per_s`) and go
by the cell's prefix in the other four (`solo_placed_per_s`), each
mapped to the shared reader.  The three cells that came after PR 24 get
its span metrics here as well.  `admin_ms_per_pass` and
`finalize_ms_per_eval` are listed only where a wave exists.
"""

from __future__ import annotations

import sys
from typing import Dict

from benchmark import host_spans

# this PR's readers: in every cell / only where evals ride a wave
_EVERYWHERE = ("lock.worker_cpu_ms_per_pass", "lock.applier_cpu_ms_per_pass",
               "lock.other_cpu_ms_per_pass", "lock.held_share",
               "worker.dequeue_ms_per_drain", "runtime.gc_ms_per_pass",
               "device.idle_gc_share", "stream.send_ms_per_eval")
_ON_A_WAVE = ("worker.finalize_ms_per_eval", "worker.admin_ms_per_pass")
# PR 24's, for the cells that `host_spans.CELLS` does not know
_PR24 = ("worker.prepare_ms_per_eval", "worker.plan_wait_ms_per_eval",
         "worker.settle_ms_per_eval", "worker.unnamed_share",
         "device.idle_unnamed_share")


def _named(prefix: str, readers) -> Dict[str, str]:
    """{name in the cell: reader}.  Under a prefix a reader's own layer
    joins its name (`lock.held_share` is `solo.lock_held_share`), as
    the cells' files name their shared readings
    (`solo.device_idle_share`, and `solo.gc_s_in_window` for a
    `runtime.*`, `solo.plan_wait_ms_per_eval` for a `worker.*`)."""
    if not prefix:
        return {r: r for r in readers}
    out = {}
    for r in readers:
        layer, _, rest = r.partition(".")
        out[prefix + "." + (rest if layer in ("worker", "runtime")
                            else layer + "_" + rest)] = r
    return out


EXTRA: Dict[str, Dict[str, str]] = {
    "csi50k-drain": _named("", _EVERYWHERE + _ON_A_WAVE),
    "spread5k-drain": _named("solo", _EVERYWHERE),
    "system50k-drain": _named("system", _PR24 + _EVERYWHERE),
    "gpu50k-drain": _named("gpu", _PR24 + _EVERYWHERE + _ON_A_WAVE),
    "spread50k-mixed": _named("mixed", _PR24 + _EVERYWHERE + _ON_A_WAVE),
}


def main(argv=None) -> int:
    for cell, extra in EXTRA.items():
        host_spans.CELLS[cell] = {**host_spans.CELLS.get(cell, {}), **extra}
    return host_spans.main(argv)


if __name__ == "__main__":
    sys.exit(main())
