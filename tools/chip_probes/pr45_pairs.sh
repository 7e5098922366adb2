#!/bin/bash
# PR 45: parent against change (both unpacked archives under .bench_archive/,
# the change's `git archive $(git write-tree)`), through pairs.sh.
#   chiprun --timeout 3300 -- bash tools/chip_probes/pr45_pairs.sh first|final
# first: ALS and JoyAI cells, 3 untraced pairs + 1 traced each (call 1)
# final: ALS 4 untraced pairs, the window cell 2 untraced pairs (the final tree)
set -u
export CHANGE=$(pwd)/.bench_archive/change
P=$(pwd)/.bench_archive/parent
echo "JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-unset}"
if [ "${1:-first}" = first ]; then
  bash tools/chip_probes/pairs.sh pr45.als $P wgde-d128.serve-steady 40 0 4501 4502 4503
  bash tools/chip_probes/pairs.sh pr45.als.traced $P wgde-d128.serve-steady 40 1 4504
  bash tools/chip_probes/pairs.sh pr45.joyai $P joyai-flash-l5.serve-steady 40 0 4505 4506 4507
  bash tools/chip_probes/pairs.sh pr45.joyai.traced $P joyai-flash-l5.serve-steady 40 1 4508
else
  bash tools/chip_probes/pairs.sh pr45.final.als $P wgde-d128.serve-steady 40 0 4511 4512 4513 4514
  bash tools/chip_probes/pairs.sh pr45.final.window $P trinity-large-l5.serve-steady 40 0 4515 4516
fi
