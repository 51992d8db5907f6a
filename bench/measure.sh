#!/bin/bash
# Measure one cell on the chip this machine holds, to set its bounds and
# its limit from: two sets of runs on the same seeds, traced runs, runs on
# further seeds, then the spread of each metric and, where seeds are given
# for it, the control (over <control units> units of work a seed, default
# 1).  Every output goes under <out dir>/.  A first run that exits non-zero
# or is not correct ends the measurement.
#
#   bench/measure.sh <workload> <out dir> "<set seeds>" "<traced seeds>" \
#       "<further seeds>" "<control seeds>" [<control units>]
W=$1; O=$2; mkdir -p "$O"
S=$(python3 -c "import json; print(json.load(open('BENCHMARK.json'))['run_seconds'])")
run() { # tag seed trace
  local t0=$SECONDS
  python3 bench/run_cell.py --workload "$W" --seed "$2" --seconds "$S" \
    --trace "$3" > "$O/$1.out" 2> "$O/$1.err"
  local rc=$?
  echo "== $1 seed $2 trace $3 rc $rc wall $((SECONDS - t0)) s"
  cat "$O/$1.out" >> "$O/${1%_*}.all"
  tail -n 1 "$O/$1.out" | python3 -c "
import json, sys
r = json.loads(sys.stdin.read())
print(json.dumps({k: r[k] for k in ('correct', 'attempted', 'failed')}),
      {k: v['value'] for k, v in r['metrics'].items()}, r['checks'],
      r['device'].get('memory_peak_bytes'), r['device'].get('busy_s'),
      r['device'].get('window_s'))" 2>/dev/null || tail -n 5 "$O/$1.err"
}
i=0; for s in $3; do i=$((i+1)); run "set1_$i" "$s" 0
  if [ $i = 1 ] && ! tail -n 1 "$O/set1_1.out" | grep -q '"correct": true'; then
    echo "first run failed: stopping"; tail -n 30 "$O/set1_1.err"; exit 1
  fi
done
i=0; for s in $3; do i=$((i+1)); run "set2_$i" "$s" 0; done
i=0; for s in $4; do i=$((i+1)); run "trace_$i" "$s" 1; done
i=0; for s in $5; do i=$((i+1)); run "extra_$i" "$s" 0; done
python3 bench/spread.py "$O"/*.all
if [ -n "$6" ]; then
  python3 bench/control.py --workload "$W" --seeds "${6// /,}" \
    --units "${7:-1}" \
    > "$O/control.out" 2> "$O/control.err"
  echo "control rc $?"; cat "$O/control.out"
fi
for f in "$O"/trace_*.out; do
  [ -f "$f" ] && tail -n 1 "$f" | python3 -c "
import json, sys
print(json.dumps(json.loads(sys.stdin.read())['breakdown']))"
done
