"""Device-asking evals that took the solo path (one eval a pass, the
exact scan, instances assigned one allocation at a time) instead of a
wave: the program's counter `nomad.device.evals_solo`, summed over the
admission rules that label it.  It must read 0 where every eval is
admitted to the batched device path.

Read once, after the window, as `system.host_fit_nodes` is: the
harness's snapshots at the window's ends (benchmark/taps.py `counters`)
hold none of the registry's counters, so this is the count since the
process started, the warm-up cycles' evals with the window's.  In a
drain they are the same traffic, and a 0 here is a 0 in the window.  A
program without the batched device path (any commit before it) reads
nothing."""

UNIT = "evals"
SERIES = "nomad.device.evals_solo"


def read(run):
    from nomad_tpu.core.telemetry import REGISTRY
    from nomad_tpu.scheduler import generic
    if not hasattr(generic, "DEVICE_BATCHED"):
        return None
    return float(REGISTRY.counter_sum(SERIES))
