"""Evals of jobs with a `network` block that `prepare_batch` kept off
the wave (one eval a pass, the exact scan, a commit waited out): the
program's counter `nomad.ports.evals_solo`, summed over the admission
rules that label it.  It must read 0 where every such eval is admitted.

Read once, after the window, as `gpu.solo_evals` is: the harness's
snapshots at the window's ends hold none of the registry's counters, so
this is the count since the process started, the warm-up cycles' evals
with the window's.  In a drain they are the same traffic, and a 0 here
is a 0 in the window.  A program whose kernels see no static port (any
commit before they did) reads nothing."""

UNIT = "evals"
SERIES = "nomad.ports.evals_solo"


def read(run):
    from nomad_tpu.core.telemetry import REGISTRY
    from nomad_tpu.ops import engine
    if not getattr(engine, "STATIC_PORT_FEASIBILITY", False):
        return None
    return float(REGISTRY.counter_sum(SERIES))
