"""The share of networked placements whose ports the per-allocation loop
assigned (one NetworkIndex round trip a row: `nomad.ports.sequential_rows`)
and not the columnar carve (`nomad.ports.batched_rows`): 100 x sequential
/ (sequential + batched).  It must read 0 where every networked eval's
rows are fresh and its network block is one the carve takes.

Read once, after the window, as `gpu.solo_evals` is: the harness's
snapshots at the window's ends hold none of the registry's counters, so
this is the share since the process started, the warm-up cycles' rows
with the window's.  In a drain they are the same traffic.  A program
whose kernels see no static port (any commit before they did) reads
nothing; one that placed no networked row reads nothing either."""

UNIT = "%"
SEQUENTIAL = "nomad.ports.sequential_rows"
BATCHED = "nomad.ports.batched_rows"


def read(run):
    from nomad_tpu.core.telemetry import REGISTRY
    from nomad_tpu.ops import engine
    if not getattr(engine, "STATIC_PORT_FEASIBILITY", False):
        return None
    sequential = float(REGISTRY.counter_sum(SEQUENTIAL))
    rows = sequential + float(REGISTRY.counter_sum(BATCHED))
    return 100.0 * sequential / rows if rows else None
