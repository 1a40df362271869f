"""Plans the applier refuted, wholly or in part, inside the window.  With
one worker it must read 0."""

UNIT = "plans"


def read(run):
    return float(run.c1["applier"]["plans_refuted"]
                 - run.c0["applier"]["plans_refuted"])
