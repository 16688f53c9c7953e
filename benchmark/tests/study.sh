# usage: bash benchmark/tests/study.sh <tag> <workload> <seconds> <n_runs> <n_trace_runs> [first_seed]
# Repeats one cell in one call and keeps every run's output under
# chiprun_out/study/. The builder's steadiness study (PERF.md). RUNNER=benchmark/tests/run_candidate.py
# runs a cell of benchmark/candidates.json.
tag=$1; wl=$2; secs=$3; n=$4; nt=$5; seed0=${6:-2147483000}
mkdir -p chiprun_out/study
for i in $(seq 1 $n); do
  python3 ${RUNNER:-benchmark/run.py} --workload $wl --seed $((seed0 + i)) --seconds $secs --trace 0 \
    > chiprun_out/study/${tag}_$i.out 2> chiprun_out/study/${tag}_$i.err
  rc=$?; cp .bench_tmp/records-$wl.json chiprun_out/study/${tag}_$i.records.json 2>/dev/null
  echo "== $tag run $i rc=$rc"; grep "^\[bench\]" chiprun_out/study/${tag}_$i.out | cut -c1-900; tail -n 1 chiprun_out/study/${tag}_$i.out | cut -c1-1200
done
for i in $(seq 1 $nt); do
  python3 ${RUNNER:-benchmark/run.py} --workload $wl --seed $((seed0 + 100 + i)) --seconds $secs --trace 1 \
    > chiprun_out/study/${tag}_t$i.out 2> chiprun_out/study/${tag}_t$i.err
  echo "== $tag trace run $i rc=$?"; grep "^\[bench\]" chiprun_out/study/${tag}_t$i.out | cut -c1-600; tail -n 1 chiprun_out/study/${tag}_t$i.out | cut -c1-4000
  grep -v cpu_aot chiprun_out/study/${tag}_t$i.err | grep -B2 -A12 "Traceback" | tail -30 | cut -c1-300
done
