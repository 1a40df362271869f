#!/bin/sh
# Runs one cell N times with consecutive seeds, as the driver measures a
# set: ./benchmark/measure.sh <cell> <label> <first seed> <runs> <seconds>
# Each run's output goes to chiprun_out/<label>_<seed>.json (stdout) and
# .err; one summary line per run is printed.  Not part of a check.
cell=$1; label=$2; seed=$3; runs=$4; seconds=$5
mkdir -p chiprun_out
i=0
while [ "$i" -lt "$runs" ]; do
  s=$((seed + i))
  python3 -m benchmark.run --workload "$cell" --seed "$s" --seconds "$seconds" \
    --trace 0 > "chiprun_out/${label}_${s}.json" 2> "chiprun_out/${label}_${s}.err"
  echo "rc=$? $cell seed=$s $(tail -n 1 "chiprun_out/${label}_${s}.json" | cut -c1-400)"
  i=$((i + 1))
done
