"""The benchmark of nomad-tpu's served scheduling path (BENCHMARK.json).

`python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>` runs one cell on the TPU this machine holds and prints
one JSON object as its last line.  Cells, configurations, traffic mixes
and per-layer metrics are files under this directory, found by name;
PERF.md says what each is for.  `python3 -m benchmark.selftest` checks
the yardstick itself on the CPU.
"""
