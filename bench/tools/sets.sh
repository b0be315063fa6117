# Full measurement of one cell: set A and set B of six runs on the same
# seeds, then six more seeds (the last three traced); those six also
# read the float8 control.  Result lines go to chiprun_out/<cell>.jsonl.
# usage: bash bench/tools/sets.sh <cell> <seconds> <seed0>
cell=$1; secs=$2; s0=$3
out=chiprun_out/$cell.jsonl
: > $out
run() {  # seed trace tag [--control]
  timeout 1200 python3 bench/run.py --workload $cell --seconds $secs --seed $1 --trace $2 $4 > chiprun_out/o.txt 2> chiprun_out/e.txt
  rc=$?
  echo "{\"tag\": \"$3\", \"seed\": $1, \"trace\": $2, \"rc\": $rc, \"result\": $(tail -1 chiprun_out/o.txt | grep '^{' || echo null)}" >> $out
  echo "$3 seed=$1 rc=$rc"; [ $rc = 0 ] || tail -5 chiprun_out/e.txt
}
for set in A B; do for i in 0 1 2 3 4 5; do run $((s0 + i)) 0 $set; done; done
for i in 6 7 8; do run $((s0 + i)) 0 X --control; done
for i in 9 10 11; do run $((s0 + i)) 1 T --control; done
