# PR 38, call 5: call 4's three other-cell pairs read -1.3, -0.6 and -1.5 % (byte-equal programs, each
# inside its cell's spread, all with the parent first or the change cold): the same cells again with
# the CHANGE first, and the chat cell, which call 4 left out.
set -u
ROOT=$PWD
OUT=$ROOT/chiprun_out/pr38/call5
mkdir -p $OUT
run() { # side dir workload seed
  local out=$OUT/$1_$3_seed$4_t0.log T1=$SECONDS
  ( cd $2 && BENCHMARK_RECORD_DIR=$OUT/$1_$3_$4_t0 python3 -m benchmark.run --workload $3 --seed $4 --seconds 45 --trace 0 ) > $out 2> $out.err
  echo "== $1 $3 seed=$4 rc=$? after $((SECONDS - T1)) s (call at $SECONDS s) $(tail -n 1 $out | python3 -c "
import json,sys
line=json.loads(sys.stdin.readline()); m=line['metrics']
print(line['correct'], line['failed'], {k: round(v['value'],4) for k,v in m.items() if k.startswith(('serve_tokens_per_s','setup_s','req_latency'))})")"
}
run change .proof kimi-serve-backlog 3800050101
run parent .parent kimi-serve-backlog 3800050101
run change .proof gpt2s-serve-backlog 3800050202
run parent .parent gpt2s-serve-backlog 3800050202
run change .proof gpt2s-serve-chat 3800050303
run parent .parent gpt2s-serve-chat 3800050303
