#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

Drives the two normal entry points once, in one process, at the full
width and depth of the one model both support — the registry's ``gpt``
(GPT-2-small: 12 layers, hidden 768, 12 heads x 64, vocab 30,522, bf16)
with random weights made from a seed:

- ``train``: ``cli.train`` (the body of ``python -m ...cli.train``) for a
  handful of steps at seq 512, global batch 32, flash attention, fused LM
  loss, rbg PRNG, on the seeded synthetic corpus;
- ``serve``: the parameters that run produced, exported with
  ``serving.export_generator`` (ragged, stepwise, paged; traced on the
  chip) and served by an in-process ``serving_http.PredictServer`` whose
  ``:generate`` route is hit over real HTTP.

``--multichip`` (four chips) runs instead — and only — the train phase
over ``--mesh data=4`` and the one-device run of the same seed and global
batch it is compared with.

One JSON object per phase goes to stdout; the LAST line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
The script needs a TPU: it exits non-zero with ``"ok": false`` when JAX
finds none (there is no CPU path here — tests/test_chip_smoke.py drives
the phase functions on the CPU at tiny sizes), and when any phase raises.
The timings it prints are smoke timings, not benchmark numbers.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import json
import os
import sys
import tempfile
import time
import traceback
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

#: greedy tokens may legitimately differ between two correct programs
#: only at a near-tie: the logit gap at the first divergence must lie
#: within this many bf16 ulps (2**-8 relative) of the logits' scale
TIE_ULPS = 8
#: loss trajectories of the four-chip and the one-chip run: same seed,
#: same global batch and dropout masks, bf16 sums in another order —
#: relative tolerance per step (measured 4.6e-5 on four v5e chips)
MULTICHIP_LOSS_RTOL = 1e-3


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def device_block() -> dict:
    """The ``device`` block of the last line, as JAX reports it. No TPU,
    or a TPU the repo's peaks table does not know, is an error."""
    import jax

    from distributed_tensorflow_example_tpu.runtime.device import (
        chip_peak_flops)
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        raise RuntimeError(
            f"chip_smoke needs a TPU; JAX found {d.platform!r} "
            f"({d.device_kind})")
    chip_peak_flops(d)        # raises on a device_kind not in the table
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def train_phase(workdir: str, *, model: str = "gpt", seq_len: int = 512,
                batch_size: int = 32, steps: int = 8,
                mesh_flag: str | None = None, mesh=None,
                expect_custom_call: bool = True, label: str = "train"):
    """Train ``steps`` steps through the CLI's own code path and check
    the run: finite, falling loss, and a compiled step whose text holds
    (or, ``expect_custom_call=False``, does not hold) the flash kernel's
    ``tpu_custom_call``. Returns ``(report, TrainRun)``."""
    import numpy as np

    from distributed_tensorflow_example_tpu.cli import train as cli_train

    metrics_path = os.path.join(workdir, f"{label}_metrics.jsonl")
    argv = ["--model", model, "--seq_len", str(seq_len),
            "--batch_size", str(batch_size), "--dtype", "bfloat16",
            "--attention", "flash", "--lm_loss_impl", "fused",
            "--prng_impl", "rbg", "--step_timing",
            "--optimizer", "adamw", "--learning_rate", "1e-3",
            "--train_steps", str(steps), "--seed", "0",
            "--log_every_steps", "1", "--summary_every_steps", "1",
            "--metrics_path", metrics_path]
    if mesh_flag:
        argv += ["--mesh", mesh_flag]
    run = cli_train.run(argv, mesh=mesh)

    with open(metrics_path) as f:
        records = [json.loads(line) for line in f]
    losses = [r["loss"] for r in records
              if "loss" in r and "step" in r and "config" not in r]
    # --log_every_steps 1: one step_timing record per step after the
    # first dispatch, each the host clock around step + block_until_ready
    step_ms = [round(r["step_timing_ms"]["mean"], 2) for r in records
               if "step_timing_ms" in r]
    step_text = run.trainer.sync.step.as_text()
    report = {
        "phase": label, "model": model, "seq_len": seq_len,
        "batch_size": batch_size, "steps": len(losses),
        "mesh": dict(run.trainer.mesh.shape),
        "compile_seconds": round(
            run.trainer.sync.last_compile_seconds, 2),
        "step_ms": step_ms,
        "loss_first": losses[0], "loss_last": losses[-1],
        "losses": losses,
        "flash_custom_calls": step_text.count("tpu_custom_call"),
        "all_reduces": step_text.count("all-reduce"),
    }
    emit(report)
    if len(losses) != steps or len(step_ms) < steps - 1:
        raise AssertionError(
            f"{label}: {len(losses)} loss and {len(step_ms)} timing "
            f"records for {steps} steps")
    if not np.all(np.isfinite(losses)):
        raise AssertionError(f"{label}: non-finite loss in {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"{label}: loss did not fall: {losses}")
    if bool(report["flash_custom_calls"]) != expect_custom_call:
        raise AssertionError(
            f"{label}: compiled step holds "
            f"{report['flash_custom_calls']} tpu_custom_call(s), expected "
            f"{'some (the flash kernel)' if expect_custom_call else 'none'}"
            " — the XLA fallback or interpret mode ran instead")
    return report, run


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def _post(port: int, name: str, payload: dict) -> dict:
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/models/{name}:generate",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    # urlopen raises HTTPError on any status but 2xx: a failed request
    # fails the phase
    with urllib.request.urlopen(req, timeout=600) as r:
        if r.status != 200:
            raise AssertionError(f":generate answered {r.status}")
        return json.loads(r.read())


def _tie_tolerance(logits) -> float:
    import numpy as np
    return TIE_ULPS * 2.0 ** -8 * max(1.0, float(np.max(np.abs(logits))))


def serve_phase(workdir: str, model, params, *, slots: int = 8,
                block_size: int = 128, prompt_len: int = 128,
                max_new: int = 128, platforms=("tpu",),
                expect_custom_call: bool = True) -> dict:
    """Export the paged stepwise generator, serve it in-process over
    HTTP, and hold the answers to the live ``decode_impl="loop"``
    oracle."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_tensorflow_example_tpu.serving import export_generator
    from distributed_tensorflow_example_tpu.serving_http import (
        PredictServer)

    export_dir = os.path.join(workdir, "export")
    t0 = time.perf_counter()
    export_generator(model, params, export_dir, ragged=True, stepwise=True,
                     paged=True, slots=slots, block_size=block_size,
                     prompt_len=prompt_len, max_new_tokens=max_new,
                     platforms=tuple(platforms))
    export_seconds = time.perf_counter() - t0
    # which attention path the decode program rides, from its own text:
    # the Mosaic kernel shows as a tpu_custom_call in the StableHLO
    with open(os.path.join(export_dir, "decode.stablehlo"), "rb") as f:
        decode_kernel = b"tpu_custom_call" in f.read()

    vocab = model.cfg.vocab_size
    rs = np.random.RandomState(0)
    # warm-up, A (alone, then repeated), and four concurrent prompts
    prompts = rs.randint(110, vocab, (6, prompt_len)).astype(np.int32)
    warm, a, conc = prompts[0], prompts[1], prompts[2:]

    srv = PredictServer(export_dir, port=0).start()
    try:
        def generate(prompt):
            return _post(srv.port, srv.name,
                         {"inputs": {"input_ids": [prompt.tolist()]}})

        t0 = time.perf_counter()
        generate(warm)                      # compiles prefill + decode
        warmup_seconds = time.perf_counter() - t0
        alone = generate(a)
        repeat = generate(a)
        with concurrent.futures.ThreadPoolExecutor(len(conc)) as pool:
            together = list(pool.map(generate, conc))
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/stats", timeout=60) as r:
            stats = json.loads(r.read())["generate"]

        # first-token logits straight from the served prefill program
        # (prompt A into block 1 of a fresh pool) for the logits check
        sw = srv.engine.sw
        prompt_blocks = sw.step_meta["prompt_blocks"]
        pre = sw.prefill({
            **sw.make_pool(),
            "input_ids": jnp.asarray(a[None]),
            "prompt_mask": jnp.ones((1, prompt_len), jnp.int32),
            "table_row": jnp.arange(1, 1 + prompt_blocks,
                                    dtype=jnp.int32)})
        served_first = np.asarray(pre["logits"][0], np.float32)
        del pre
    finally:
        srv.stop()

    answers = [alone] + together
    asked = np.stack([a, *conc])
    got = np.asarray([r["generations"][0] for r in answers], np.int32)
    if got.shape != (len(asked), max_new):
        raise AssertionError(f"serve: generations shaped {got.shape}, "
                             f"expected {(len(asked), max_new)}")
    if repeat["generations"] != alone["generations"]:
        raise AssertionError("serve: the repeated prompt's tokens differ "
                             "from the first answer's")

    # ---- the oracle: live generate through the per-layer loop ---------
    dev_params = jax.device_put(params)
    want = np.asarray(jax.jit(
        lambda p, ids: model.generate(p, ids, max_new,
                                      decode_impl="loop"))(
        dev_params, jnp.asarray(asked)))
    # teacher-forced logits of the full forward over prompt + oracle
    # tokens: what the near-tie and first-token checks read
    seqs = np.concatenate([asked, want], axis=1)
    fwd = np.asarray(jax.jit(
        lambda p, ids: model.apply(
            p, {}, {"input_ids": ids,
                    "attention_mask": jnp.ones_like(ids)})[0])(
        dev_params, jnp.asarray(seqs)), np.float32)

    first_diff = float(np.max(np.abs(served_first
                                     - fwd[0, prompt_len - 1])))
    first_tol = _tie_tolerance(fwd[0, prompt_len - 1])
    divergences = []
    for i in range(len(asked)):
        differ = np.nonzero(got[i] != want[i])[0]
        if not differ.size:
            continue
        d = int(differ[0])
        # both streams agree up to d, so this is the oracle's own
        # context: the two candidates must be a near-tie in its logits
        row = fwd[i, prompt_len + d - 1]
        divergences.append({
            "request": i, "at_token": d,
            "oracle_token": int(want[i, d]), "served_token": int(got[i, d]),
            "logit_gap": float(row[want[i, d]] - row[got[i, d]]),
            "tolerance": _tie_tolerance(row)})

    timings = [t for r in answers for t in r["timings"]]
    report = {
        "phase": "serve", "slots": slots, "block_size": block_size,
        "prompt_len": prompt_len, "max_new": max_new,
        "export_seconds": round(export_seconds, 2),
        "warmup_seconds": round(warmup_seconds, 2),
        "decode_attention": ("pallas kernel (tpu_custom_call)"
                             if decode_kernel else "xla gather"),
        "requests": len(answers) + 2,
        "exact_vs_loop": len(asked) - len(divergences),
        "compared": len(asked),
        "divergences": divergences,
        "first_token_logits_max_abs_diff": first_diff,
        "first_token_logits_tolerance": first_tol,
        "smoke_ttft_ms": [round(t["queue_ms"] + t["prefill_ms"], 2)
                          for t in timings],
        "smoke_per_token_ms": [
            round(t["decode_ms"] / max(1, t["tokens"] - 1), 3)
            for t in timings],
        "decode_steps": stats["decode_steps"],
        "steps_shared": stats["steps_shared"],
        "prefills": stats["prefills"],
        "prefix_cache_hits": stats["prefix_cache_hits"],
        "requests_failed": stats["requests_failed"],
    }
    emit(report)
    if decode_kernel != expect_custom_call:
        raise AssertionError(
            f"serve: decode program rides {report['decode_attention']}, "
            f"expected {'the Pallas kernel' if expect_custom_call else 'the XLA path'}")
    if first_diff > first_tol:
        raise AssertionError(
            f"serve: first-token logits differ from the full forward by "
            f"{first_diff} > {first_tol}")
    bad = [d for d in divergences
           if abs(d["logit_gap"]) > d["tolerance"]]
    if bad:
        raise AssertionError(f"serve: tokens diverge from the loop "
                             f"oracle beyond a near-tie: {bad}")
    if stats["decode_steps"] < max_new - 1:
        raise AssertionError(f"serve: /stats shows {stats['decode_steps']}"
                             " decode dispatches")
    if stats["prefix_cache_hits"] != 1:
        raise AssertionError(
            f"serve: /stats shows {stats['prefix_cache_hits']} "
            "prefix-cache hits, expected exactly the repeated prompt's")
    if stats["requests_failed"]:
        raise AssertionError(f"serve: {stats['requests_failed']} "
                             "request(s) failed in the engine")
    return report


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def multichip_phase(workdir: str, *, devices=None, model: str = "gpt",
                    seq_len: int = 512, batch_size: int = 32,
                    steps: int = 8, expect_custom_call: bool = True
                    ) -> dict:
    """Synchronous replica training over ``data=N`` against the
    one-device run of the same seed and global batch."""
    import jax
    import numpy as np

    from distributed_tensorflow_example_tpu.config import MeshShape
    from distributed_tensorflow_example_tpu.parallel.mesh import build_mesh

    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    kw = dict(model=model, seq_len=seq_len, batch_size=batch_size,
              steps=steps, expect_custom_call=expect_custom_call)
    # all devices: cli.train builds the mesh itself (build_mesh's
    # create_device_mesh branch on a real TPU host)
    many, run = train_phase(
        workdir, mesh_flag=f"data={n}", label=f"train_data{n}",
        mesh=(None if devices == list(jax.devices())
              else build_mesh(MeshShape(data=n), devices=devices)), **kw)
    state, sync = run.state, run.trainer.sync
    placed = sync.shard_batch({k: v[:batch_size] for k, v in
                               run.trainer.train_arrays.items()})
    parked = [
        jax.tree_util.keystr(path)
        for path, leaf in jax.tree_util.tree_leaves_with_path(
            {"state": state, "batch": placed})
        if len(leaf.sharding.device_set) != n]
    shard_rows = {k: int(v.addressable_shards[0].data.shape[0])
                  for k, v in placed.items()}
    del run, state, sync, placed

    one, run = train_phase(
        workdir, mesh_flag="data=1", label="train_data1",
        mesh=build_mesh(MeshShape(data=1), devices=devices[:1]), **kw)
    del run

    rel = np.abs(np.asarray(many["losses"]) - np.asarray(one["losses"])) \
        / np.abs(one["losses"])
    report = {"phase": "multichip", "devices": n,
              "loss_max_rel_diff": float(rel.max()),
              "loss_rtol": MULTICHIP_LOSS_RTOL,
              "per_device_batch_rows": shard_rows,
              "arrays_not_on_all_devices": parked,
              "all_reduces": many["all_reduces"]}
    emit(report)
    if parked:
        raise AssertionError(f"multichip: arrays not spread over all {n} "
                             f"devices: {parked[:8]}")
    if set(shard_rows.values()) != {batch_size // n}:
        raise AssertionError(f"multichip: per-device batch shard "
                             f"{shard_rows}, expected {batch_size // n}")
    if not many["all_reduces"]:
        raise AssertionError("multichip: no all-reduce in the compiled "
                             "step")
    if rel.max() > MULTICHIP_LOSS_RTOL:
        raise AssertionError(
            f"multichip: loss trajectories differ by {rel.max()} > "
            f"{MULTICHIP_LOSS_RTOL}: {many['losses']} vs {one['losses']}")
    return report


# ---------------------------------------------------------------------------

def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--multichip", action="store_true",
                    help="four chips: only data=4 training and the "
                         "one-device run it is compared with")
    args = ap.parse_args(argv)
    device = None
    try:
        device = device_block()
        import jax

        from distributed_tensorflow_example_tpu.runtime.device import (
            enable_compilation_cache)
        emit({"phase": "start", "device": device,
              "compilation_cache": enable_compilation_cache()})
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as work:
            if args.multichip:
                if device["count"] != 4:
                    raise RuntimeError(
                        f"--multichip needs four chips, JAX found "
                        f"{device['count']}")
                multichip_phase(work)
            else:
                _, run = train_phase(work)
                model = run.trainer.model
                params = jax.device_get(run.state.params)
                del run           # the serve phase needs the chip's memory
                serve_phase(work, model, params)
    except (Exception, SystemExit):   # report the failure, then fail
        traceback.print_exc()
        emit({"ok": False, "device": device})
        return 1
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
