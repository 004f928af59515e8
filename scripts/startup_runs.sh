#!/usr/bin/env bash
# Benchmark runs for reading start-up (PR 51), several in one chip call so that
# they share one machine and its compile cache:
#
#   chiprun --chips 1 --timeout 3500 -- bash scripts/startup_runs.sh <out> <seconds> <side>:<cell>:<seed>:<trace> ...
#
# No run is started once <seconds> of the call are over, so that the call ends
# of itself and brings back what it has.
#
# <side> is "change" (this tree) or "parent": the parent commit's tree,
# unpacked by the caller beforehand to benchmark/out/parent (git archive; the
# chip's copy has no .git), over which this tree's BENCHMARK.json and
# benchmark/ are laid, as the driver lays them for its traced runs. Where the
# machine sets JAX_COMPILATION_CACHE_DIR (it did: PR 51, call 152) both sides
# share that cache, else each has its own <tree>/.jax_cache; a cell's first
# run is cold for what the cache lacks and its next one warm. Every run leaves under
# chiprun_out/<out>/ its output, its `startup` record and, traced, its
# start-up spans (fixtures/make_startup_fixture.py), check_startup.py's
# reading of them and check_join.py's verdict.
set -u
out=chiprun_out/$1; budget=$2; shift 2
mkdir -p "$out"
root=$PWD
if [ -d benchmark/out/parent ]; then
  cp BENCHMARK.json benchmark/out/parent/BENCHMARK.json
  for f in benchmark/*; do
    case $f in benchmark/out|benchmark/data) ;;
      *) cp -r "$f" benchmark/out/parent/benchmark/ ;;
    esac
  done
fi
for run in "$@"; do
  if [ "$SECONDS" -ge "$budget" ]; then echo "not started: $run"; continue; fi
  IFS=: read -r side cell seed trace <<<"$run"
  tree=$root; [ "$side" = parent ] && tree=$root/benchmark/out/parent
  name=$side-$cell-$seed-t$trace
  (cd "$tree" && python3 benchmark/run.py --workload "$cell" --seed "$seed" \
     --seconds 30 --trace "$trace") >"$out/$name.txt" 2>"$out/$name.err"
  echo "$name rc=$? $(grep -o 'set-up: setup_s=[0-9.]*' "$out/$name.txt")" \
       "$(grep -o '"samples_per_s_chip": {"value": [0-9.]*' "$out/$name.txt" | head -1)"
  dir=$tree/benchmark/out/runs/$cell/seed$seed-trace$trace
  grep '"startup"' "$dir/metrics.jsonl" >"$out/$name.startup.jsonl"
  if [ "$trace" = 1 ]; then
    step=$(grep -o 'log intervals, steps [0-9]*' "$out/$name.txt" | grep -o '[0-9]*$')
    python3 benchmark/fixtures/make_startup_fixture.py "$dir/spans.jsonl" \
       "$out/$name.spans.json.gz" "$step" >/dev/null
    python3 benchmark/check_startup.py "$dir/spans.jsonl" "$step" >"$out/$name.report.txt" 2>&1
    python3 benchmark/check_join.py "$dir" >"$out/$name.join.txt" 2>&1
    echo "  check_join rc=$? $(head -1 "$out/$name.report.txt")"
  fi
done
