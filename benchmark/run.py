"""One run of one cell.

    python3 -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's configuration and traffic by the names in
``BENCHMARK.json``, warms up (set-up), measures for ``--seconds``, checks
the timed path's outputs against the plain reference, and prints one JSON
object as the last line of stdout. Fails, with no result line, without a
TPU or on a ``device_kind`` missing from ``benchmark/peaks.json``.

``--rehearse 1`` (``benchmark/tests`` only) drives the same control flow
on the CPU at the tiny sizes the data files keep under ``rehearsal``; its
line is marked ``"rehearsal": true`` and carries no metric.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse          # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import shutil            # noqa: E402
import sys               # noqa: E402
import tempfile          # noqa: E402
import traceback         # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import trace_reduce                      # noqa: E402
from benchmark.manifest import Manifest                 # noqa: E402


class Env:
    """What a kind needs of one run."""

    def __init__(self, manifest, args):
        self.manifest = manifest
        self.cell = manifest.cell(args.workload)
        self.config = manifest.config(self.cell)
        self.traffic = manifest.traffic(self.cell)
        self.seed = int(args.seed)
        self.window_seconds = float(args.seconds)
        self.trace = bool(args.trace)
        self.rehearse = bool(args.rehearse)
        self.warm_steps = int(self.pick(self.traffic, "warm_steps", 5))
        self.trace_seconds = float(self.pick(self.traffic, "trace_seconds",
                                             3.0))
        self.t_process = T_PROCESS
        self.workdir = tempfile.mkdtemp(prefix="benchmark_run_")
        self.trace_dir = os.path.join(self.workdir, "trace")
        self.reduced_trace = None
        #: tests swap this for a function that breaks the timed path
        self.break_program = lambda **kw: None

    def pick(self, d: dict, key: str, default=None):
        """``d[key]``, or under a rehearsal the tiny ``d["rehearsal"][key]``."""
        if self.rehearse and key in d.get("rehearsal", {}):
            return d["rehearsal"][key]
        return d.get(key, default) if default is not None else d[key]

    def note(self, text: str) -> None:
        print(text, flush=True)

    def mark(self, what: str) -> None:
        """A set-up span's end, seconds after the process started."""
        self.note(f"set-up: {what} at "
                  f"{time.perf_counter() - self.t_process:.2f}s")

    def start_trace(self) -> None:
        import jax
        jax.profiler.start_trace(self.trace_dir)

    def stop_trace(self) -> None:
        import jax
        jax.profiler.stop_trace()
        path = trace_reduce.find_xplane(self.trace_dir)
        self.reduced_trace = trace_reduce.reduce(path)
        keep = os.environ.get("BENCHMARK_KEEP_TRACE")
        if keep:        # look at a trace by hand: its planes, lines, names
            os.makedirs(keep, exist_ok=True)
            with open(os.path.join(keep, "describe.txt"), "w") as f:
                f.write("\n".join(trace_reduce.describe(path)))
            with open(os.path.join(keep, "reduced.json"), "w") as f:
                json.dump(self.reduced_trace, f)
            if os.path.getsize(path) < 8 << 20:
                shutil.copy(path, os.path.join(keep, "trace.xplane.pb"))

    def memory_peak_bytes(self) -> int:
        import jax
        stats = [d.memory_stats() or {} for d in jax.local_devices()]
        self.note(f"memory_stats: {json.dumps(stats[0])}")
        # the TPU runtime keeps the programs' scratch space apart, as
        # reserved bytes: the chip's peak is buffers plus that
        return int(max(s.get("peak_bytes_in_use", 0)
                       + s.get("peak_bytes_reserved", 0) for s in stats))

    def cleanup(self) -> None:
        shutil.rmtree(self.workdir, ignore_errors=True)


def device_block(env: Env) -> tuple[dict, dict | None]:
    """The devices as JAX reports them; no accelerator, too few chips or
    an unknown ``device_kind`` is an error, never a default."""
    import jax
    devices = jax.devices()
    d = devices[0]
    block = {"platform": d.platform, "kind": d.device_kind,
             "count": len(devices)}
    if env.rehearse:
        return block, None
    if d.platform != "tpu":
        raise RuntimeError(f"the benchmark needs a TPU; JAX found "
                           f"{d.platform!r} ({d.device_kind})")
    if len(devices) < env.cell["chips"]:
        raise RuntimeError(f"cell {env.cell['name']!r} needs "
                           f"{env.cell['chips']} chip(s), JAX found "
                           f"{len(devices)}")
    return block, env.manifest.peak(d.device_kind)


def decide(env: Env, compared: dict) -> bool:
    """Each number compared, printed beside its limit."""
    limits = env.pick(env.manifest.limits(env.cell["name"]), "limits")
    ok = True
    for name, value in compared.items():
        limit = limits[name]
        good = bool(value <= limit)
        ok &= good
        env.note(f"compared {name}: value {value!r} limit {limit!r} "
                 f"{'ok' if good else 'NOT CORRECT'}")
    missing = set(limits) - set(compared)
    if missing:
        env.note(f"compared: no reading for {sorted(missing)}: NOT CORRECT")
        ok = False
    return ok


def result_line(env: Env, device: dict, peak: dict | None, out: dict) -> dict:
    name = env.cell["name"]
    correct = decide(env, out["compared"])
    line = {"correct": correct, "attempted": int(out["attempted"]),
            "failed": int(out["failed"]), "metrics": {}, "device": device}
    if env.rehearse:
        line["rehearsal"] = True
        line["counts"] = out.get("counts", {})
        return line
    device["memory_peak_bytes"] = int(out["memory_peak_bytes"])
    if not env.trace:
        values = dict(out["values"], setup_s=out["setup_s"])
        for m in env.manifest.end_to_end(name):
            line["metrics"][m["name"]] = {"value": values[m["name"]],
                                          "unit": m["unit"]}
        return line
    reduced = env.reduced_trace
    if reduced is None or not reduced["busy_s"] > 0:
        raise RuntimeError("the traced window holds no device operation")
    device["busy_s"] = reduced["busy_s"]
    device["window_s"] = reduced["window_s"]
    ctx = dict(out["ctx"], trace=reduced, peak=peak,
               chips=env.cell["chips"], setup_s=out["setup_s"])
    for m in env.manifest.per_layer(name):
        value = env.manifest.read_metric(m, ctx)
        if value is not None:
            line["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
    line["breakdown"] = trace_reduce.breakdown(reduced, env.traffic["kind"])
    return line


def prepare(rehearse) -> None:
    """Before JAX is imported: one fixed cache directory inside the
    checkout (the path is part of the cache's key, and the program takes
    this one where it is set); a rehearsal is held to the CPU."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"


def main(argv: list[str] | None = None, *, env_hook=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--rehearse", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    prepare(args.rehearse)
    env = None
    try:
        env = Env(Manifest(ROOT), args)
        if env_hook is not None:
            env_hook(env)
        device, peak = device_block(env)
        out = env.manifest.kind(env.traffic).run(env)
        line = result_line(env, device, peak, out)
    except (Exception, SystemExit):     # no result line, a non-zero code
        traceback.print_exc()
        return 1
    finally:
        if env is not None:
            env.cleanup()
    keep = os.environ.get("BENCHMARK_RECORD_DIR")
    if keep:            # every run made on the chip is kept (records/)
        os.makedirs(keep, exist_ok=True)
        name = f"{args.workload}_seed{args.seed}_trace{args.trace}.json"
        with open(os.path.join(keep, name), "w") as f:
            json.dump({"argv": vars(args), "line": line,
                       "compared": out["compared"],
                       "record": out.get("record")}, f)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
