import sys
from benchmark import planted_state, run as bench_run
fault, seed, trace = sys.argv[1:4]
def hook(env):
    env.traffic["check_requests"] = 48
    if fault != "none":
        planted_state.hook_for(fault)(env)
rc = bench_run.main(["--workload", "kimi-serve-backlog", "--seed", seed, "--seconds", "45", "--trace", trace], env_hook=hook)
if planted_state.DROPPED:
    print(f"planted: state dropped at {planted_state.DROPPED}", file=sys.stderr, flush=True)
sys.exit(rc)
