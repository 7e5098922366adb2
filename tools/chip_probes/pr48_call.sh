#!/bin/bash
# PR 48's chip calls: `first` as made; `final` was asked for 35 times over
# 1 h 55 min after it and never given a machine, so it is what a later
# session would run, not a record (both sides unpacked archives under
# .bench_archive/: see pr48_setup.sh; `kernel` is a copy of `change` with the
# three `@_shared` lines above `_before`, `_between` and `_behind` taken out
# of `models/window_moe.py`: a rung in tiles then shares its two kernels
# alone, as PR 42 had it).
#   chiprun --timeout 3500 -- bash tools/chip_probes/pr48_call.sh first|final
J=joyai-flash-l5.serve-steady; W=trinity-large-l5.serve-steady
S="bash tools/chip_probes/pr48_setup.sh"
if [ "${1:-first}" = first ]; then
  # the change's programs are new to the cache, and the parent's may be: one
  # set-up alone a side compiles them
  $S pr48.first change:$J:4800000101:0:SETUP_IN_CELL_STOP=1 \
     parent:$J:4800000100:0:SETUP_IN_CELL_STOP=1
  # the claimed cell: four pairs, whole runs, the order alternating
  $S pr48.first parent:$J:4800000102:0 change:$J:4800000102:0 \
     change:$J:4800000103:0 parent:$J:4800000103:0 \
     parent:$J:4800000104:0 change:$J:4800000104:0 \
     change:$J:4800000105:0 parent:$J:4800000105:0
  # the window cell's set-up alone: the parent, the tile segments shared, the
  # kernels alone shared as PR 42 had them (trace and lowering read cold too)
  $S pr48.first parent:$W:4800000106:0:SETUP_IN_CELL_STOP=1 \
     change:$W:4800000107:0:SETUP_IN_CELL_STOP=1 \
     kernel:$W:4800000108:0:SETUP_IN_CELL_STOP=1
  bash tools/chip_probes/pr48_bits.sh pr48.first.bits; echo "bits rc=$?"
  $S pr48.first parent:$W:4800000109:0 change:$W:4800000109:0 \
     change:$J:4800000110:1 change:$W:4800000111:1
else
  # the first call showed the machine's 192 MiB cache evicting between cells
  # (a side's programs compiled cold again two cells later), so the claimed
  # cell starts with a set-up alone a side.  Two more pairs of it, the parent
  # traced on the seeds the change was traced on in the first call, under
  # this PR's benchmark files (its line must come without the two new
  # metrics, not fail), and a pair of the ALS cell, whose only changed code
  # is `RungPrograms`' timer
  A=wgde-d128.serve-steady
  $S pr48.final parent:$J:4800000121:0:SETUP_IN_CELL_STOP=1 \
     change:$J:4800000122:0:SETUP_IN_CELL_STOP=1 \
     change:$J:4800000123:0 parent:$J:4800000123:0 \
     parent:$J:4800000124:0 change:$J:4800000124:0 \
     parent:$J:4800000110:1
  $S pr48.final parent:$A:4800000128:0 change:$A:4800000128:0 \
     parent:$W:4800000111:1
fi
