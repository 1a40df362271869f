"""csi50k-mesh4: csi50k's fleet, volumes, jobs and checker on a server
whose engine shards the node axis over the host's four chips.

Nothing of the deployment's data is defined here: sizes come from
csi50k-mesh4.json (`cfg`), and the builders are csi50k's own, found by
name (`cfg["fleet_of"]`).  What differs is the layout, which the cell
asks for with `chips` 4: benchmark/run.py then leaves the Agent its
default, the node axis over every visible device.
"""

from __future__ import annotations

import sys

from benchmark.loader import load_json, load_module

EXIT_NO_PROGRAM = 5        # benchmark/run.py's code for "nothing to run"


def _require_named_programs() -> None:
    """The cell's device readings count placement kernels by name
    (`jit_place*`; the roofline reader `jit_place_multi_compact_sharded*`).
    A program whose sharded programs are anonymous closures (`jit_f`,
    `jit_f_chained`) finishes an untraced run and fails every traced one
    after a full window, and half a result is worse than none.  Said at
    load, before a fleet is built or a job is sent."""
    from nomad_tpu.parallel import mesh

    if not hasattr(mesh, "PROGRAM_NAMES"):
        print("benchmark: csi50k-mesh4 needs a program whose sharded "
              "programs are named (nomad_tpu/parallel/mesh.py "
              "PROGRAM_NAMES); this program's are not", file=sys.stderr)
        raise SystemExit(EXIT_NO_PROGRAM)


_require_named_programs()

_csi50k = load_module(
    "configs", load_json("configs", "csi50k-mesh4")["fleet_of"])
build_fleet = _csi50k.build_fleet
install = _csi50k.install
make_job = _csi50k.make_job


# csi50k's check, whole: counts, zones, datacenters, capacity.  What
# sharding alone could break and node ids cannot show (WHICH feasible
# node a placement took) is held against the single-device path by
# tests/test_mesh_served.py and chip_smoke.py --mesh-legs; what it could
# break and node ids do show (a pick of a padding row, a shard's rows
# committed to another shard's nodes) reads here as an unknown node, a
# zone breach or a node over its capacity.  Nothing else was found by
# reading or by the chip runs (PERF.md section 6, PR 36).
check = _csi50k.check
