#!/bin/bash
# PR 41 call D: DeepSeek-V2's set-up, parent / change / change / parent on one machine (each side's second run finds its programs in the cache)
O=/root/repo/chiprun_out/pr41d; mkdir -p $O
T0=$(date +%s)
run() {
  name=$1; shift
  "$@" > $O/$name.out 2> $O/$name.err; echo "$name rc=$? at $(( $(date +%s) - T0 )) s"
  tail -n 1 $O/$name.out | cut -c1-420
  grep -h "^route " $O/$name.err | tr '\n' ';'; echo
}
B="python3 /root/repo/.scratch/run_with_route.py"
P=/root/repo/.archive_tree/parent; C=/root/repo/.archive_tree/change
cd $P; run ds_parent_2 $B --workload deepseek_v2_serve_reason --seed 4100008003 --seconds 50 --trace 0
cd $C; run ds_change_2 $B --workload deepseek_v2_serve_reason --seed 4100008003 --seconds 50 --trace 0
cd $C; run ds_change_3 $B --workload deepseek_v2_serve_reason --seed 4100009007 --seconds 50 --trace 0
cd $P; run ds_parent_3 $B --workload deepseek_v2_serve_reason --seed 4100009007 --seconds 50 --trace 0
echo "done at $(( $(date +%s) - T0 )) s"
