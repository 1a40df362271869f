#!/usr/bin/env python3
"""Records the small profiler trace the self-check reduces
(benchmark/selftest/data/small_trace.xplane.pb).  Run once on the chip:

    chiprun -- python3 benchmark/selftest/record_small_trace.py

Three launches of one named jitted program with idle gaps between them,
an anchor annotation at the start; prints what the reducer should find.
"""

import glob
import json
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp


def main() -> int:
    dev = jax.devices()[0]
    print("device", dev.platform, dev.device_kind, len(jax.devices()))
    out = os.path.join("chiprun_out", "small_trace")
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out, exist_ok=True)

    def place_probe(x):
        def body(c, _):
            return jnp.tanh(c @ c) * 0.5, ()
        y, _ = jax.lax.scan(body, x, None, length=50)
        return y

    fn = jax.jit(place_probe)
    x = jnp.ones((512, 512), jnp.float32)
    fn(x).block_until_ready()                    # compile outside the trace
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
    except AttributeError:
        opts = None
    kw = {"profiler_options": opts} if opts is not None else {}
    jax.profiler.start_trace(out, **kw)
    t_anchor = time.monotonic()
    with jax.profiler.TraceAnnotation("benchmark_anchor"):
        pass
    stamps = []
    for _ in range(3):
        t0 = time.monotonic()
        fn(x).block_until_ready()
        stamps.append((t0 - t_anchor, time.monotonic() - t_anchor))
        time.sleep(0.02)
    t_end = time.monotonic() - t_anchor
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    size = os.path.getsize(path)
    dst = os.path.join("chiprun_out", "small_trace.xplane.pb")
    shutil.copy(path, dst)
    shutil.rmtree(out, ignore_errors=True)
    print(json.dumps({"launch_host_intervals_s": stamps, "t_end_s": t_end,
                      "bytes": size}))
    # what is in it, for whoever writes the reducer
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(dst)
    for plane in pd.planes:
        print("PLANE", repr(plane.name))
        for line in plane.lines:
            evs = list(line.events)
            names = {}
            for e in evs:
                names[e.name] = names.get(e.name, 0) + 1
            top = sorted(names.items(), key=lambda kv: -kv[1])[:8]
            first = evs[0] if evs else None
            print("  LINE", repr(line.name), len(evs),
                  (first.start_ns, first.duration_ns) if first else None,
                  top)
    return 0


if __name__ == "__main__":
    sys.exit(main())
