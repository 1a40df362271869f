"""Mean evaluations per batched launch (the flight recorder's `items` per
wave) inside the window.  A backlog must read eval_batch (64); part-
filled waves under steady arrivals read fewer."""

UNIT = "evals"


def read(run):
    items = [w["items"] for w in run.tap_window["waves"] if "items" in w]
    return sum(items) / len(items) if items else None
