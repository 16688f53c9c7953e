# usage: bash benchmark/tests/proof.sh <workload> <seconds> <n_runs> <set>
# One set of runs of one cell in one call, each run with another seed, the
# same seeds in every set. Prints each run's last line. RUNNER=benchmark/tests/run_candidate.py
# runs a cell of benchmark/candidates.json.
wl=$1; secs=$2; n=$3; set=$4
mkdir -p chiprun_out/proof
for i in $(seq 1 $n); do
  seed=$((1800000000 + 7919 * i))
  python3 ${RUNNER:-benchmark/run.py} --workload $wl --seed $seed --seconds $secs --trace 0 \
    > chiprun_out/proof/${wl}_s${set}_$i.out 2> chiprun_out/proof/${wl}_s${set}_$i.err
  echo "== $wl set $set run $i seed $seed rc=$?"; grep "client statistics\|first-step\|per-token" chiprun_out/proof/${wl}_s${set}_$i.out | cut -c1-400; tail -n 1 chiprun_out/proof/${wl}_s${set}_$i.out | cut -c1-700
done
