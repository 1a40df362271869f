"""Share of the host's materialize and commit time that ran while a launch
was in flight on the device: overlap of the `device` intervals with the
`materialize` and `commit` intervals over those stages' total, in the
window.  A prefetched wave's `device` interval spans its predecessor's
host phase, so this is the share hidden under an IN-FLIGHT launch, not
under device compute."""

UNIT = "%"


def read(run):
    from benchmark import trace_reduce as tr
    iv = run.tap_window["intervals"]
    device = tr.union((a, b) for _, a, b in iv.get("device", ()))
    host = [(a, b) for s in ("materialize", "commit")
            for _, a, b in iv.get(s, ())]
    if not device or not host:
        return None
    hidden = sum(tr.total(tr.clip(device, [h])) for h in host)
    return 100.0 * hidden / tr.total(host)
