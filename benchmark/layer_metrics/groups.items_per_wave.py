"""Mean launch items a wave inside the window: the flight recorder's
`groups` per wave, one item a task group of each eval (a wave record's
`items` counts its evals).  A wave of 64 evals of three groups each must
read 192.  A program whose wave records carry no `groups` (an item an
eval) reads nothing."""

UNIT = "items"


def read(run):
    groups = [w["groups"] for w in run.tap_window["waves"] if "groups" in w]
    return sum(groups) / len(groups) if groups else None
