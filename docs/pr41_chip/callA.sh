#!/bin/bash
# PR 41 call A: the prefill programs and a decode step by scope, parent then change, one machine
out=/root/repo/chiprun_out/pr41; mkdir -p $out
(cd /root/repo/.archive_tree/parent && python tools/servescope.py --workload dots3_note_serve_longctx --depth 4 --prefill-lens 16384,8192,4096 > $out/scope_parent.log 2>&1); echo "parent rc=$?"
(python tools/servescope.py --workload dots3_note_serve_longctx --depth 4 --prefill-lens 16384,8192,4096 > $out/scope_change.log 2>&1); echo "change rc=$?"
tail -c 600 $out/scope_change.log
