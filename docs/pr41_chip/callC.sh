#!/bin/bash
# PR 41 call C: the final tree (git archive of the staged tree) against the parent, one machine:
# the claimed cell traced on both sides, two more pairs, DeepSeek-V2's traced pair, the smoke
O=/root/repo/chiprun_out/pr41c; mkdir -p $O
T0=$(date +%s)
left() { echo $(( ${BUDGET:-3400} - ($(date +%s) - T0) )); }
run() {
  name=$1; shift
  if [ $(left) -lt ${NEED:-360} ]; then echo "SKIP $name ($(left) s left)"; return; fi
  "$@" > $O/$name.out 2> $O/$name.err; echo "$name rc=$? at $(( $(date +%s) - T0 )) s"
  tail -n 1 $O/$name.out | cut -c1-${CUT:-700}
  grep -h "^compared mean_gap\|^compared widest_gap\|^route " $O/$name.err | tr '\n' ';'; echo
}
B="python3 /root/repo/.scratch/run_with_route.py"
P=/root/repo/.archive_tree/parent; C=/root/repo/.archive_tree/change
cd $C; CUT=6000 run dots_change_traced $B --workload dots3_note_serve_longctx --seed 4100004003 --seconds 50 --trace 1
cd $P; CUT=6000 run dots_parent_traced $B --workload dots3_note_serve_longctx --seed 4100004003 --seconds 50 --trace 1
cd $P; run dots_parent_3 $B --workload dots3_note_serve_longctx --seed 4100005009 --seconds 50 --trace 0
cd $C; run dots_change_3 $B --workload dots3_note_serve_longctx --seed 4100005009 --seconds 50 --trace 0
cd $C; run dots_change_4 $B --workload dots3_note_serve_longctx --seed 4100006011 --seconds 50 --trace 0
cd $P; run dots_parent_4 $B --workload dots3_note_serve_longctx --seed 4100006011 --seconds 50 --trace 0
cd $C; CUT=6000 run ds_change_traced $B --workload deepseek_v2_serve_reason --seed 4100007001 --seconds 50 --trace 1
cd $P; CUT=6000 run ds_parent_traced $B --workload deepseek_v2_serve_reason --seed 4100007001 --seconds 50 --trace 1
cd $C; NEED=300 run smoke python chip_smoke.py
echo "done at $(( $(date +%s) - T0 )) s"
