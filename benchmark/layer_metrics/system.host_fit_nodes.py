"""Nodes the system scheduler fit-checked on the host, one `allocs_fit`
each, instead of in the `place_system` launch: the program's counter
`nomad.system.host_fit_nodes`.  It must read 0 where every eval is
admitted to the device path.

Read once, after the window: the harness's snapshots at the window's
ends (benchmark/taps.py `counters`) hold none of the registry's
counters, so this is the count since the process started, the warm-up
cycles' evals with the window's.  In a drain they are the same traffic,
and a 0 here is a 0 in the window.  A program without the counter (any
commit before it) reads nothing."""

UNIT = "nodes"
SERIES = "nomad.system.host_fit_nodes"


def read(run):
    from nomad_tpu.core.telemetry import REGISTRY
    counters = REGISTRY.snapshot()["counters"]
    return float(counters[SERIES]) if SERIES in counters else None
