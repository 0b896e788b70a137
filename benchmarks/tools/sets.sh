#!/bin/bash
# usage: sets.sh <cell> <tag> <trace seeds...> -- <set seeds...>
cell=$1; tag=$2; shift 2
mkdir -p chiprun_out
tr=(); while [ "$1" != "--" ]; do tr+=("$1"); shift; done; shift
run() { # seed trace label
  python3 benchmarks/run.py --workload $cell --seed $1 --seconds 45 --trace $2 > chiprun_out/_o.txt 2> chiprun_out/_e.txt; rc=$?
  echo "{\"label\":\"$3\",\"seed\":$1,\"trace\":$2,\"rc\":$rc,\"line\":$(tail -n 1 chiprun_out/_o.txt | grep '^{' || echo null)}" >> chiprun_out/sets_$tag.jsonl
  grep "comparison took\|^set-up\|^window" chiprun_out/_e.txt | cut -c1-900 >> chiprun_out/sets_$tag.err
  echo "$3 seed $1 trace $2 rc=$rc $(tail -n 1 chiprun_out/_o.txt | cut -c1-260)"
}
for s in "$@"; do run $s 0 set1; done
for s in "$@"; do run $s 0 set2; done
for s in "${tr[@]}"; do run $s 1 trace; done
