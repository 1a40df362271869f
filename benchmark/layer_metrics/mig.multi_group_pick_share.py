"""Share of the wave placements with a device ask that landed on a node
advertising several device groups: 100 x the program's counter
`nomad.device.picks{node=multi_group}` over the counter's sum over
`node` (ops/engine.py collect_batch, one a placement).  Read once, after
the window, as `gpu.solo_evals` is: the count since the process started,
the warm-up cycles' with the window's, the same traffic in a drain.  A
program without the counter (any commit before it) reads nothing."""

UNIT = "%"
SERIES = "nomad.device.picks"


def read(run):
    from nomad_tpu.core.telemetry import REGISTRY
    by_node = {label.partition("=")[2]: n for label, n in
               REGISTRY.counter_labels(SERIES).items()}
    total = sum(by_node.values())
    if not total:
        return None
    return 100.0 * by_node.get("multi_group", 0) / total
