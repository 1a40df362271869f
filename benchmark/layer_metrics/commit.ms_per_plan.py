"""Mean `commit` interval of the plans committed inside the timed
intervals: the applier's evaluate plus the state-store upsert for one
plan (the applier records it per plan, without a wave).  Plans of the
untimed parts of a cycle, such as a deregistration's stops, are left out."""

UNIT = "ms"


def read(run):
    timed = run.result["timed"]
    ms = [(b - a) * 1e3
          for _, a, b in run.tap_window["intervals"].get("commit", ())
          if any(t0 <= a <= t1 for t0, t1 in timed)]
    return sum(ms) / len(ms) if ms else None
