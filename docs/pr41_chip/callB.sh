#!/bin/bash
# PR 41 call B: the claimed cell, parent / change / change / parent on one machine, then DeepSeek-V2's pair
O=/root/repo/chiprun_out/pr41b; mkdir -p $O
T0=$(date +%s)
left() { echo $(( ${BUDGET:-3300} - ($(date +%s) - T0) )); }
run() {
  name=$1; shift
  if [ $(left) -lt ${NEED:-280} ]; then echo "SKIP $name ($(left) s left)"; return; fi
  "$@" > $O/$name.out 2> $O/$name.err; echo "$name rc=$? at $(( $(date +%s) - T0 )) s"
  tail -n 1 $O/$name.out | cut -c1-${CUT:-700}
  grep -h "^compared mean_gap\|^compared widest_gap\|^route " $O/$name.err | tr '\n' ';'; echo
}
B="python3 /root/repo/.scratch/run_with_route.py"
P=/root/repo/.archive_tree/parent; C=/root/repo
cd $P; run dots_parent_1 $B --workload dots3_note_serve_longctx --seed 4100001003 --seconds 50 --trace 0
cd $C; run dots_change_1 $B --workload dots3_note_serve_longctx --seed 4100001003 --seconds 50 --trace 0
cd $C; run dots_change_2 $B --workload dots3_note_serve_longctx --seed 4100002017 --seconds 50 --trace 0
cd $P; run dots_parent_2 $B --workload dots3_note_serve_longctx --seed 4100002017 --seconds 50 --trace 0
cd $P; run ds_parent_1 $B --workload deepseek_v2_serve_reason --seed 4100003001 --seconds 50 --trace 0
cd $C; run ds_change_1 $B --workload deepseek_v2_serve_reason --seed 4100003001 --seconds 50 --trace 0
echo "done at $(( $(date +%s) - T0 )) s"
