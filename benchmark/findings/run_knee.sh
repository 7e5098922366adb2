#!/bin/bash
# A configuration's knee from two sweeps in one call: a coarse one over the
# rates given, then a fine one around the coarse knee (0.8-1.2 x).  Both
# tables stay in chiprun_out/sweep.<config>.<traffic>.{coarse,fine}.json.
#   chiprun --timeout 1500 -- bash benchmark/findings/run_knee.sh <config> <traffic> <seconds> <rate,rate,...>
config=$1; traffic=$2; seconds=$3; rates=$4
python3 benchmark/sweep.py --config $config --traffic $traffic --rates $rates \
  --seconds $seconds --seed 3400000101 --tag .coarse 2>&1 | grep -E '^\{' | cut -c1-600
knee=$(python3 -c "import json; print(json.load(open('chiprun_out/sweep.$config.$traffic.coarse.json'))['knee_rps'] or 0)")
echo "coarse knee: $knee"
fine=$(python3 -c "k=float('$knee') or float('$rates'.split(',')[0]); print(','.join(str(round(k*f,1)) for f in (0.8,0.9,1.0,1.1,1.2)))")
python3 benchmark/sweep.py --config $config --traffic $traffic --rates $fine \
  --seconds $seconds --seed 3400000201 --tag .fine 2>&1 | grep -E '^\{' | cut -c1-600
