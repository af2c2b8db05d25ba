"""Trainer entrypoint — the reference example script, TPU-native.

Preserves the reference's CLI surface (SURVEY.md §2.1, §3.1;
BASELINE.json:5): ``--ps_hosts --worker_hosts --job_name --task_index``
plus model/training knobs. The launch pattern ports unchanged::

    python -m distributed_tensorflow_example_tpu.cli.train \
        --job_name=worker --task_index=0 \
        --worker_hosts=host0:port,host1:port --model=mlp

``--job_name=ps`` prints the no-PS-on-TPU notice and exits 0, so the
reference's per-role launch scripts keep working (SURVEY.md §7 item 3).
"""

from __future__ import annotations

import argparse
import os
import sys
from typing import Any, NamedTuple

from ..cluster import ClusterSpec, WORKER_JOB
from ..config import (CheckpointConfig, DataConfig, MeshShape,
                      ObservabilityConfig, OptimizerConfig, SyncConfig,
                      TrainConfig, add_legacy_flags, anomaly_settings,
                      flash_attention_kwargs, lm_loss_settings,
                      parse_hosts)
from ..utils.logging import get_logger

log = get_logger("cli")

# dataset-name aliases (one definition: the --augment gate, the dataset
# dispatch, and the transform wiring must never disagree)
CIFAR_DATASETS = ("resnet20", "cifar10", "cifar")
IMAGENET_DATASETS = ("resnet50", "imagenet")


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        description="TPU-native sync data-parallel trainer "
                    "(distributed-tensorflow-example parity CLI)")
    add_legacy_flags(p)
    p.add_argument("--model", default="mlp",
                   help="mlp | pipe_mlp | lenet | resnet20 | resnet50 | "
                        "bert | bert_large | bert_tiny | moe_bert | "
                        "moe_bert_tiny | pipe_bert | pipe_bert_tiny | "
                        "pipe_moe_bert | pipe_moe_bert_tiny | "
                        "gpt | gpt_tiny | sdar_moe | sdar_moe_tiny "
                        "(served decoders: export only)")
    p.add_argument("--num_layers", type=int, default=0,
                   help="block-description models (sdar_moe): how many "
                        "layers to build (default 0: the model's own)")
    p.add_argument("--dataset", default=None,
                   help="default: the model's canonical dataset")
    p.add_argument("--data_dir", default=None,
                   help="real dataset directory; omit for synthetic data")
    p.add_argument("--native", action="store_true",
                   help="use the C++ native loader when built (falls back "
                        "to the Python loader if unavailable)")
    p.add_argument("--streaming", action="store_true",
                   help="decode-per-batch streaming input pipeline "
                        "(bounded memory; ImageNet-scale folder trees)")
    p.add_argument("--fast_decode", action="store_true",
                   help="JPEG DCT-domain downscale decode for the "
                        "streaming train split (~1.9x decode throughput; "
                        "pixels deviate slightly from the plain decode)")
    p.add_argument("--augment", action="store_true",
                   help="training augmentation (train split only): "
                        "ImageNet random-resized crop + flip (requires "
                        "--streaming) or CIFAR pad-4 crop + flip")
    p.add_argument("--label_offset", type=int, default=0,
                   help="TFRecord image shards: added to every label "
                        "(tf-slim ImageNet writes 1-indexed labels: "
                        "pass -1)")
    p.add_argument("--max_per_class", type=int, default=None,
                   help="cap eagerly-decoded images per class (ImageNet "
                        "folder loading; full train split is ~770GB as f32)")
    p.add_argument("--seq_len", type=int, default=128,
                   help="BERT sequence length (must be <= model max_len)")
    p.add_argument("--batch_size", type=int, default=128,
                   help="GLOBAL batch size")
    p.add_argument("--train_steps", type=int, default=1000)
    p.add_argument("--steps_per_loop", type=int, default=1,
                   help="training steps per device dispatch (lax.scan "
                        "inner loop; hook cadences must be multiples)")
    p.add_argument("--max_inflight_steps", type=int, default=0,
                   help="block the host every N trained steps, bounding "
                        "the async dispatch queue (0 = unbounded, the "
                        "normal fast path; set small — e.g. 1-2 — on "
                        "runtime stacks that misbehave under deep "
                        "dispatch queues)")
    p.add_argument("--learning_rate", type=float, default=0.5)
    p.add_argument("--optimizer", default="sgd", type=str.lower,
                   choices=["sgd", "momentum", "adam", "adamw",
                            "lars", "lamb", "adafactor"],
                   help="base optimizer (lars/lamb: the large-batch "
                        "ImageNet/BERT recipes for sync-DP scaling; "
                        "adafactor: factored second moments, the "
                        "T5/TPU memory-frugal recipe — NOTE its "
                        "--weight_decay is a constant per-step rate, "
                        "not LR-scaled like adamw's)")
    p.add_argument("--momentum", type=float, default=0.9)
    p.add_argument("--weight_decay", type=float, default=0.0)
    p.add_argument("--wd_mask", default="exclude_1d",
                   choices=["exclude_1d", "all"],
                   help="weight-decay mask: exclude_1d (standard; biases "
                        "and LayerNorm scales undecayed) or all")
    p.add_argument("--warmup_steps", type=int, default=0,
                   help="linear LR warmup steps")
    p.add_argument("--decay_schedule", default="constant",
                   choices=["constant", "cosine", "linear", "piecewise",
                            "exponential", "polynomial", "natural_exp",
                            "inverse_time"])
    p.add_argument("--decay_steps", type=int, default=0,
                   help="exponential/natural_exp/inverse_time: steps per "
                        "decay_factor application (tf.train decay-family "
                        "parity; required for those three); polynomial: "
                        "absolute step where decay bottoms out (falls "
                        "back to --train_steps)")
    p.add_argument("--end_learning_rate", type=float, default=0.0,
                   help="polynomial: floor LR (tf.train.polynomial_decay)")
    p.add_argument("--decay_power", type=float, default=1.0,
                   help="polynomial: exponent (1.0 = linear BERT recipe)")
    p.add_argument("--decay_boundaries", default="",
                   help="comma-separated steps where piecewise LR drops "
                        "(e.g. '30000,60000,80000')")
    p.add_argument("--decay_factor", type=float, default=0.1,
                   help="piecewise: LR multiplier at each boundary; "
                        "exponential: decay rate per decay_steps")
    p.add_argument("--moe_experts", type=int, default=None,
                   help="MoE models: experts per MoE layer (default: "
                        "the model's; moe_bert=8)")
    p.add_argument("--moe_top_k", type=int, default=None,
                   help="MoE models: routed experts per token (1 = "
                        "Switch; 2 = classic top-2 gating)")
    p.add_argument("--moe_capacity_factor", type=float, default=None,
                   help="MoE models: per-expert slot headroom "
                        "C = ceil(T/E * factor); overflow tokens drop "
                        "to the residual path")
    p.add_argument("--moe_every", type=int, default=None,
                   help="MoE models: MoE FFN every k-th layer "
                        "(default: the model's; moe_bert=2)")
    p.add_argument("--moe_aux_weight", type=float, default=None,
                   help="MoE models: load-balancing aux-loss weight "
                        "(default: the model's; moe_bert=0.01)")
    p.add_argument("--moe_router_z_weight", type=float, default=None,
                   help="MoE models: ST-MoE router z-loss weight "
                        "(typ. 1e-3; 0 disables)")
    p.add_argument("--moe_jitter", type=float, default=None,
                   help="MoE models: router input noise amplitude "
                        "U[1-j, 1+j], training only (typ. 0.01)")
    p.add_argument("--lm_loss_impl", default=None,
                   choices=["full", "chunked", "fused"],
                   help="LM-head loss strategy (gpt/bert families): "
                        "full = materialize [B,S,vocab] logits (parity "
                        "oracle / kill switch); chunked = seq chunks "
                        "under jax.checkpoint (gpt only; needs "
                        "--lm_loss_chunk); fused = blockwise vocab scan "
                        "with custom VJP — the logits tensor never "
                        "exists in fwd or bwd and token_accuracy rides "
                        "the same pass (default: full, or chunked when "
                        "--lm_loss_chunk is set)")
    p.add_argument("--lm_loss_vocab_block", type=int, default=None,
                   help="fused LM loss: vocab tile of the blockwise "
                        "scan (0 = the built-in default; swept by "
                        "experiments/vocab_chain_sweep.py); requires "
                        "--lm_loss_impl fused")
    p.add_argument("--token_accuracy_every_n", type=int, default=1,
                   help="gpt models: compute the per-step "
                        "token_accuracy argmax only every n-th step on "
                        "the full/chunked paths (costs ~3.2 ms/step at "
                        "the 30k vocab — BASELINE.md; skipped steps "
                        "publish -1.0; rejected with --lm_loss_impl "
                        "fused, whose accuracy is free)")
    p.add_argument("--lm_loss_chunk", type=int, default=None,
                   help="gpt models: sequence-chunked LM loss — at most "
                        "[B, chunk, vocab] logits resident; must divide "
                        "seq_len; 0 = full. The pre-fused fallback: "
                        "--lm_loss_impl fused removes the full tensor "
                        "from both passes without the recompute")
    p.add_argument("--label_smoothing", type=float, default=0.0,
                   help="smooth training targets (image classifiers: "
                        "lenet/resnet20/resnet50; the standard ImageNet "
                        "recipe uses 0.1)")
    p.add_argument("--grad_clip_norm", type=float, default=0.0,
                   help="global-norm gradient clipping (0 disables)")
    p.add_argument("--grad_clip_value", type=float, default=0.0,
                   help="elementwise |g| clipping (tf.clip_by_value "
                        "parity; 0 disables; composes with the norm "
                        "clip)")
    p.add_argument("--export_dir", default=None,
                   help="write a serving artifact (StableHLO via "
                        "jax.export, params baked in, batch-polymorphic) "
                        "after training — the SavedModel-parity path")
    p.add_argument("--export_generator", default=None, metavar="DIR",
                   help="write a DECODE artifact (the whole KV-cache "
                        "generation as one StableHLO program, params "
                        "baked) after training — causal-LM models "
                        "(gpt/gpt_tiny) only; shape/sampling come from "
                        "the --gen_* flags")
    p.add_argument("--gen_prompt_len", type=int, default=128,
                   help="prompt length the generator artifact accepts "
                        "(static shape)")
    p.add_argument("--gen_max_new", type=int, default=128,
                   help="tokens the generator artifact emits")
    p.add_argument("--gen_batch", type=int, default=1,
                   help="generator artifact batch size (static; the "
                        "REST server pads smaller requests)")
    p.add_argument("--gen_temperature", type=float, default=0.0,
                   help="0 = greedy; > 0 samples (artifact then takes "
                        "a seed)")
    p.add_argument("--gen_top_k", type=int, default=0,
                   help="sample from the k most likely tokens only "
                        "(0 = off; needs --gen_temperature > 0)")
    p.add_argument("--gen_top_p", type=float, default=0.0,
                   help="nucleus sampling: smallest token set with "
                        "cumulative probability >= p (0 = off; needs "
                        "--gen_temperature > 0)")
    p.add_argument("--gen_eos_id", type=int, default=None,
                   help="stop a row at this token id (emitted, then "
                        "--gen_pad_id fills the tail; the decode loop "
                        "exits early device-side when every row is "
                        "done)")
    p.add_argument("--gen_pad_id", type=int, default=0,
                   help="tail filler after --gen_eos_id fires")
    p.add_argument("--gen_ragged", action="store_true",
                   help="artifact additionally takes a prompt_mask "
                        "feature (1 = real token) for ragged prompt "
                        "batches")
    p.add_argument("--gen_weight_quant", default="off",
                   choices=["off", "int8"],
                   help="quantize the artifact's decode weights "
                        "symmetric per-output-channel int8 (scales + "
                        "quant metadata recorded; dequant inside the "
                        "stacked scan, so int8 is what crosses HBM per "
                        "layer step). LOSSY — gated by the documented "
                        "greedy-drift bound, not byte parity. The "
                        "paged-pool companion --kv_cache_dtype lives "
                        "on the serving export surfaces "
                        "(export_generator / experiments/"
                        "serving_load.py); it needs paged=True, which "
                        "this CLI's monolithic export does not build")
    p.add_argument("--warm_start", default=None,
                   help="checkpoint file/dir to initialize params from "
                        "when starting fresh (tf.train.init_from_"
                        "checkpoint parity; a checkpoint in --ckpt_dir "
                        "always wins)")
    p.add_argument("--warm_start_map", default="",
                   help="assignment map 'ckpt_prefix:model_prefix' "
                        "pairs, comma-separated (default: same paths)")
    p.add_argument("--ema_decay", type=float, default=0.0,
                   help="shadow-param EMA decay "
                        "(tf.train.ExponentialMovingAverage parity; "
                        "0 disables; eval runs on the shadow)")
    p.add_argument("--ema_debias", action="store_true",
                   help="tf num_updates ramp: min(decay, (1+n)/(10+n))")
    p.add_argument("--moment_dtype", default="float32",
                   choices=["float32", "bfloat16"],
                   help="optimizer first-moment storage dtype (Adam mu / "
                        "momentum buffer); bf16 halves its HBM traffic "
                        "and checkpoint size, update math stays f32")
    p.add_argument("--accum_steps", type=int, default=1)
    p.add_argument("--dtype", default="float32",
                   choices=["float32", "bfloat16"])
    p.add_argument("--param_dtype", default="float32",
                   choices=["float32", "bfloat16"],
                   help="parameter storage dtype (f32 default; bf16 halves "
                        "param/optimizer HBM at some precision cost)")
    p.add_argument("--bn_stats_dtype", default="float32",
                   choices=["float32", "bfloat16"],
                   help="BatchNorm batch-statistic reduction dtype (conv "
                        "models; running stats stay f32 — the ResNet "
                        "byte-roofline experiment knob)")
    p.add_argument("--mesh", default="",
                   help="axis sizes, e.g. 'data=4,model=2' (default: all "
                        "devices on the data axis)")
    p.add_argument("--sync_mode", default="auto",
                   choices=["auto", "shard_map"])
    p.add_argument("--attention", default="xla", choices=["xla", "flash"],
                   help="attention implementation for transformer models "
                        "(flash = Pallas kernel, wins at long sequences)")
    p.add_argument("--attention_block_q", type=int, default=0,
                   help="flash kernel fwd Q-tile rows (multiple of 8; "
                        "0 = chosen by the kernel from S, D and dtype); "
                        "requires --attention flash — "
                        "experiments/flash_sweep.py sweeps this")
    p.add_argument("--attention_block_k", type=int, default=0,
                   help="flash kernel fwd K-tile columns (multiple of "
                        "128; 0 = chosen by the kernel); requires "
                        "--attention flash")
    p.add_argument("--attention_bwd_block", type=int, default=0,
                   help="flash kernel bwd tile for both streamed dims "
                        "(multiple of 128; 0 = the fwd tiles where one "
                        "is set, else chosen by the kernel); requires "
                        "--attention flash")
    p.add_argument("--attention_bwd", default="auto",
                   choices=["auto", "split", "fused"],
                   help="flash backward variant: auto = chosen by the "
                        "kernel (fused while its dq slab fits VMEM); "
                        "split = two-kernel FA-2 decomposition; fused = "
                        "one kernel computing dq+dk+dv (scores "
                        "recomputed once, ~29%% fewer bwd matmul FLOPs; "
                        "runs split where the slab does not fit); split "
                        "and fused require --attention flash")
    p.add_argument("--prng_impl", default="threefry2x32",
                   choices=["threefry2x32", "rbg", "unsafe_rbg"],
                   help="PRNG key implementation for the training rng "
                        "stream; rbg uses the TPU's native generator "
                        "(BERT-base measured 112.4->89.1 ms/step: dropout-"
                        "mask generation dominates threefry's TPU cost)")
    p.add_argument("--remat", default="none",
                   choices=["none", "full", "dots"],
                   help="jax.checkpoint each transformer layer: backward "
                        "recomputes activations instead of keeping them in "
                        "HBM ('full' saves only layer boundaries, 'dots' "
                        "also keeps matmul outputs); long-context enabler")
    p.add_argument("--ckpt_dir", default=None)
    p.add_argument("--save_steps", type=int, default=0)
    p.add_argument("--save_secs", type=float, default=0.0)
    p.add_argument("--max_to_keep", type=int, default=5)
    p.add_argument("--keep_best_metric", default=None,
                   help="track this eval metric and keep the best "
                        "checkpoint outside the rotation ring "
                        "(BestExporter parity; needs --eval_every_steps "
                        "or a final eval)")
    p.add_argument("--keep_best_mode", default="max",
                   choices=["max", "min"],
                   help="max (accuracy-like) or min (loss-like)")
    p.add_argument("--keep_checkpoint_every_n_hours", type=float, default=0.0,
                   help="pin one checkpoint outside the max_to_keep ring "
                        "every N hours (TF Saver semantics; 0 disables)")
    p.add_argument("--async_save", action="store_true",
                   help="write checkpoints on a background thread (the "
                        "reference's checkpoint-thread behavior)")
    p.add_argument("--sharded_save", action="store_true",
                   help="sharded checkpoints (TF Saver sharded=True "
                        "parity): each host writes only the parameter "
                        "shards it owns, in parallel — no cross-host "
                        "gather; restore reads back selectively")
    p.add_argument("--log_every_steps", type=int, default=100)
    p.add_argument("--summary_every_steps", type=int, default=0,
                   help="scalar-summary cadence to the metrics JSONL "
                        "(SummarySaverHook parity; 0 disables)")
    p.add_argument("--param_histograms_every_steps", type=int, default=0,
                   help="weight-histogram cadence "
                        "(tf.summary.histogram parity: full "
                        "HistogramProtos to --tb_logdir, summary stats "
                        "to the JSONL; 0 disables)")
    p.add_argument("--metrics_path", default=None)
    p.add_argument("--tb_logdir", default=None,
                   help="write TensorBoard scalar event files here "
                        "(tf.summary FileWriter parity; no TF dependency)")
    p.add_argument("--eval_every_steps", type=int, default=0)
    p.add_argument("--early_stop_metric", default=None,
                   help="stop training when this eval metric stops "
                        "improving (stop_if_no_decrease_hook parity; "
                        "needs --eval_every_steps)")
    p.add_argument("--early_stop_patience", type=int, default=3,
                   help="evals without improvement before stopping")
    p.add_argument("--early_stop_mode", default="max",
                   choices=["max", "min"])
    p.add_argument("--eval_only", action="store_true",
                   help="no training: restore the latest checkpoint from "
                        "--ckpt_dir (or --eval_step N), run the eval "
                        "pass, print one JSON metrics line, exit")
    p.add_argument("--eval_step", type=int, default=None,
                   help="checkpoint step to evaluate (--eval_only; "
                        "default: latest)")
    p.add_argument("--eval_best", action="store_true",
                   help="with --eval_only: evaluate (and, with "
                        "--export_dir, export) the checkpoint the "
                        "keep_best tracker recorded instead of latest")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--on_anomaly", default="halt",
                   choices=["halt", "skip", "rollback"],
                   help="policy for steps whose loss or global grad-norm "
                        "is non-finite (on-device detection, no per-step "
                        "host sync; every policy keeps the bad update out "
                        "of the training state): halt = stop with a "
                        "summary; skip = identity update, keep training; "
                        "rollback = restore the last VERIFIED checkpoint "
                        "and replay the data stream (needs --ckpt_dir + "
                        "--save_steps)")
    p.add_argument("--max_anomalies", type=int, default=10,
                   help="anomaly budget for skip/rollback: halt with a "
                        "summary once more anomalous steps than this are "
                        "observed (0 = halt on the first)")
    p.add_argument("--fault_spec", default="",
                   help="deterministic fault injection for chaos testing "
                        "(inert when empty): ';'-separated rules like "
                        "'ckpt.write:step=2:raise=OSError', "
                        "'loader.next:p=0.01', 'step.nan:step=7', "
                        "'ckpt.write:step=3:corrupt=truncate' — see "
                        "runtime/faults.py for the grammar")
    p.add_argument("--check_nans", action="store_true",
                   help="stop on non-finite loss (NanTensorHook parity; "
                        "per-step host sync)")
    p.add_argument("--debug_checks", action="store_true",
                   help="checkify float_checks around the compiled step: "
                        "any NaN/Inf produced inside the program raises at "
                        "the step where it occurs (debug-only cost)")
    p.add_argument("--debug_nans", action="store_true",
                   help="enable jax_debug_nans (eager NaN tracebacks)")
    p.add_argument("--profiler_port", type=int, default=0,
                   help="host a live profiler service on port + "
                        "process_index (the reference server's "
                        "ProfilerService parity; attach TensorBoard's "
                        "profile plugin on demand)")
    p.add_argument("--profile_dir", default=None)
    p.add_argument("--profile_steps", default=None,
                   help="start,stop step range for the profiler hook")
    p.add_argument("--step_timing", action="store_true",
                   help="record per-dispatch device-time percentiles + "
                        "compiled-step flops/bytes to the metrics JSONL "
                        "(WorkerCacheLogger parity; blocks the dispatch "
                        "queue per step)")
    p.add_argument("--trace_path", default=None,
                   help="dump the training-loop span lanes (data-wait/"
                        "step/checkpoint/rollback) as Perfetto-loadable "
                        "trace-event JSON here when training ends")
    p.add_argument("--trace_buffer_events", type=int, default=65536,
                   help="span ring-buffer bound for --trace_path "
                        "(oldest events drop first)")
    return p


def parse_mesh(spec: str) -> MeshShape | None:
    if not spec:
        return None
    kw = {}
    for part in spec.split(","):
        k, v = part.split("=")
        kw[k.strip()] = int(v)
    return MeshShape(**kw)


def config_from_args(args: argparse.Namespace) -> TrainConfig:
    profile_steps = None
    if args.profile_steps:
        a, b = args.profile_steps.split(",")
        profile_steps = (int(a), int(b))
    return TrainConfig(
        model=args.model,
        num_layers=args.num_layers,
        train_steps=args.train_steps,
        label_smoothing=args.label_smoothing,
        moe_experts=args.moe_experts,
        moe_top_k=args.moe_top_k,
        moe_capacity_factor=args.moe_capacity_factor,
        moe_every=args.moe_every,
        moe_aux_weight=args.moe_aux_weight,
        moe_router_z_weight=args.moe_router_z_weight,
        moe_jitter=args.moe_jitter,
        lm_loss_impl=args.lm_loss_impl,
        lm_loss_chunk=args.lm_loss_chunk,
        lm_loss_vocab_block=args.lm_loss_vocab_block,
        token_accuracy_every_n=args.token_accuracy_every_n,
        eval_every_steps=args.eval_every_steps,
        early_stop_metric=args.early_stop_metric,
        early_stop_patience=args.early_stop_patience,
        early_stop_mode=args.early_stop_mode,
        steps_per_loop=args.steps_per_loop,
        max_inflight_steps=args.max_inflight_steps,
        on_anomaly=args.on_anomaly,
        max_anomalies=args.max_anomalies,
        fault_spec=args.fault_spec,
        seed=args.seed,
        dtype=args.dtype,
        param_dtype=args.param_dtype,
        bn_stats_dtype=args.bn_stats_dtype,
        attention_impl=args.attention,
        attention_block_q=args.attention_block_q,
        attention_block_k=args.attention_block_k,
        attention_bwd_block=args.attention_bwd_block,
        attention_bwd=args.attention_bwd,
        remat=args.remat,
        prng_impl=args.prng_impl,
        mesh=parse_mesh(args.mesh) or MeshShape(data=-1),
        data=DataConfig(dataset=args.dataset or args.model,
                        data_dir=args.data_dir,
                        batch_size=args.batch_size, seed=args.seed,
                        native=args.native, seq_len=args.seq_len,
                        max_per_class=args.max_per_class,
                        label_offset=args.label_offset,
                        streaming=args.streaming, augment=args.augment,
                        fast_decode=args.fast_decode),
        optimizer=OptimizerConfig(name=args.optimizer,
                                  learning_rate=args.learning_rate,
                                  momentum=args.momentum,
                                  weight_decay=args.weight_decay,
                                  wd_mask=args.wd_mask,
                                  warmup_steps=args.warmup_steps,
                                  decay_schedule=args.decay_schedule,
                                  decay_boundaries=tuple(
                                      int(b) for b in
                                      args.decay_boundaries.split(",")
                                      if b.strip()),
                                  decay_factor=args.decay_factor,
                                  decay_steps=args.decay_steps,
                                  end_learning_rate=args.end_learning_rate,
                                  decay_power=args.decay_power,
                                  grad_clip_norm=args.grad_clip_norm,
                                  grad_clip_value=args.grad_clip_value,
                                  moment_dtype=args.moment_dtype,
                                  ema_decay=args.ema_decay,
                                  ema_debias=args.ema_debias,
                                  total_steps=args.train_steps),
        sync=SyncConfig(accum_steps=args.accum_steps, mode=args.sync_mode),
        checkpoint=CheckpointConfig(
            directory=args.ckpt_dir,
            warm_start=args.warm_start,
            warm_start_map=args.warm_start_map,
            max_to_keep=args.max_to_keep,
            keep_best_metric=args.keep_best_metric,
            keep_best_mode=args.keep_best_mode,
            save_steps=args.save_steps,
            save_secs=args.save_secs,
            keep_checkpoint_every_n_hours=args.keep_checkpoint_every_n_hours,
            async_save=args.async_save,
            sharded=args.sharded_save),
        obs=ObservabilityConfig(
            log_every_steps=args.log_every_steps,
            summary_every_steps=args.summary_every_steps,
            param_histograms_every_steps=(
                args.param_histograms_every_steps),
            metrics_path=args.metrics_path,
            tb_logdir=args.tb_logdir,
            check_nans=args.check_nans,
            debug_checks=args.debug_checks,
            debug_nans=args.debug_nans,
            profile_dir=args.profile_dir,
            profile_steps=profile_steps,
            step_timing=args.step_timing,
            trace_path=args.trace_path,
            trace_buffer_events=args.trace_buffer_events),
    )


def bert_vocab_file(data_dir: str | None) -> str | None:
    """Path of the corpus vocab.txt when ``data_dir`` is a raw-text BERT
    corpus (the text-pipeline trigger), else None."""
    if not data_dir:
        return None
    p = os.path.join(data_dir, "vocab.txt")
    return p if os.path.exists(p) else None


def _imagenet_val(data_dir: str, label_offset: int = 0) -> dict:
    """Eager val split: TFRecord shards when present, else folder tree
    (label_offset must match the train side's)."""
    from ..data.tfrecord import split_shards
    if split_shards(data_dir, "val"):
        from ..data.imagenet import load_imagenet_tfrecords
        return load_imagenet_tfrecords(data_dir, "val",
                                       label_offset=label_offset)
    from ..data.imagenet import load_imagenet_folder
    return load_imagenet_folder(data_dir, "val")


def load_dataset(cfg: TrainConfig, model=None, eval_only: bool = False):
    """Returns (train_arrays, eval_arrays) batch-keyed numpy dicts.

    Dataset defaults follow the model (BASELINE.json:7-11 pairings):
    mlp/lenet → MNIST, resnet20 → CIFAR-10, resnet50 → ImageNet.

    ``eval_only`` skips materializing the train split where that is
    expensive (ImageNet folder decode / streaming pool) and returns
    ``(None, eval_arrays)`` for those datasets.
    """
    name = cfg.data.dataset
    if cfg.data.augment and name not in (CIFAR_DATASETS
                                         + IMAGENET_DATASETS):
        raise SystemExit(
            f"--augment is an image-training recipe; dataset {name!r} "
            "has no augmentation pipeline")
    if cfg.data.fast_decode and name not in IMAGENET_DATASETS:
        raise SystemExit(
            f"--fast_decode is a JPEG decode knob (streaming ImageNet); "
            f"dataset {name!r} does not decode JPEGs")
    if eval_only and name in IMAGENET_DATASETS \
            and not cfg.data.synthetic and cfg.data.data_dir:
        v = _imagenet_val(cfg.data.data_dir, cfg.data.label_offset)
        return None, {"x": v["val_x"], "y": v["val_y"]}
    if name in ("mlp", "pipe_mlp", "mnist", "lenet"):
        from ..data.mnist import get_mnist
        # arrays stay flat-784; models normalize input shape themselves
        # (mlp flattens, lenet reshapes to NHWC)
        d = get_mnist(cfg.data.data_dir, cfg.data.synthetic)
    elif name in CIFAR_DATASETS:
        from ..data.cifar import get_cifar10
        d = get_cifar10(cfg.data.data_dir, cfg.data.synthetic)
    elif name in IMAGENET_DATASETS:
        if cfg.data.streaming and not cfg.data.synthetic:
            if not cfg.data.data_dir:
                raise SystemExit("--streaming requires --data_dir")
            # train split streams (decode-per-batch, bounded memory); the
            # eval split stays an eager array dict — UNCAPPED, same as the
            # eager path: eval numbers must be comparable regardless of
            # the train cap (see data/imagenet.py get_imagenet). Both
            # splits auto-detect TFRecord shards vs a folder tree
            from ..data.streaming import StreamingSource
            train_src = StreamingSource(
                cfg.data.data_dir, "train",
                max_per_class=cfg.data.max_per_class,
                augment=cfg.data.augment,
                fast_decode=cfg.data.fast_decode,
                label_offset=cfg.data.label_offset)
            v = _imagenet_val(cfg.data.data_dir, cfg.data.label_offset)
            return train_src, {"x": v["val_x"], "y": v["val_y"]}
        for flag, on in (("--augment", cfg.data.augment),
                         ("--fast_decode", cfg.data.fast_decode)):
            if on:
                # eager arrays are decoded once: both knobs act in the
                # streaming pipeline's per-batch decode
                raise SystemExit(
                    f"{flag} is not supported with --synthetic"
                    if cfg.data.synthetic or not cfg.data.data_dir
                    else f"{flag} requires --streaming")
        if cfg.data.data_dir and not cfg.data.synthetic:
            from ..data.tfrecord import split_shards
            if split_shards(cfg.data.data_dir, "train"):
                raise SystemExit(
                    "TFRecord ImageNet shards stream per batch — pass "
                    "--streaming (the eager path would decode the whole "
                    "train split into RAM)")
        from ..data.imagenet import get_imagenet
        d = get_imagenet(cfg.data.data_dir, cfg.data.synthetic,
                         max_per_class=cfg.data.max_per_class)
    elif name in ("gpt", "gpt_tiny"):
        from ..data.bert_data import get_lm_data
        gcfg = getattr(model, "cfg", None)
        vocab = gcfg.vocab_size if gcfg else cfg.data.vocab_size
        if gcfg and cfg.data.seq_len > gcfg.max_len:
            raise SystemExit(
                f"--seq_len {cfg.data.seq_len} exceeds the model's "
                f"max_len {gcfg.max_len}")
        return get_lm_data(cfg.data.data_dir, vocab_size=vocab,
                           seq_len=cfg.data.seq_len,
                           synthetic=cfg.data.synthetic)
    elif name in ("bert", "bert_large", "bert_tiny",
                  "moe_bert", "moe_bert_tiny",
                  "pipe_bert", "pipe_bert_tiny",
                  "pipe_moe_bert", "pipe_moe_bert_tiny"):
        from ..data.bert_data import get_bert_data
        # take vocab/prediction shapes from the MODEL so data and logits
        # can never diverge (out-of-range labels clamp silently under jit)
        bert_cfg = getattr(model, "cfg", None)
        vocab = bert_cfg.vocab_size if bert_cfg else cfg.data.vocab_size
        max_pred = bert_cfg.max_predictions if bert_cfg else 20
        seq_len = cfg.data.seq_len
        if bert_cfg and seq_len > bert_cfg.max_len:
            # defense in depth for models constructed OUTSIDE the
            # registry: the registered factories grow max_len to cover
            # seq_len, so this cannot fire for them — but positions >=
            # max_len would silently clamp the pos-embedding gather
            # under jit, so keep the hard stop for hand-built models
            raise SystemExit(
                f"--seq_len {seq_len} exceeds the model's max_len "
                f"{bert_cfg.max_len}")
        vocab_txt = bert_vocab_file(cfg.data.data_dir)
        has_npy = cfg.data.data_dir and any(
            os.path.exists(os.path.join(cfg.data.data_dir, f))
            for f in ("train.npy", "tokens.npy"))
        if vocab_txt and not has_npy and not cfg.data.synthetic:
            # raw-text corpus + local vocab.txt: tokenize + pack + mask.
            # Pre-tokenized .npy files take precedence when both exist
            # (the vocab likely produced them) — no silent path switch.
            # Cheap pre-check BEFORE tokenizing a possibly huge corpus:
            # the model's embedding table must cover every token id.
            with open(vocab_txt) as f:
                n_vocab = sum(1 for _ in f)
            if n_vocab > vocab:
                raise SystemExit(
                    f"vocab.txt has {n_vocab} tokens but the model's "
                    f"vocab_size is {vocab} (ids beyond the embedding "
                    "table clamp silently under jit). Pass --vocab_size "
                    f"{n_vocab} for bert/bert_large/moe_bert; the *_tiny "
                    "variants pin their own small vocab — shrink the "
                    "vocab or use a full-size model")
            from ..data.bert_text import get_bert_text_data
            tr, te, data_vocab = get_bert_text_data(
                cfg.data.data_dir, vocab_txt, seq_len=seq_len,
                max_predictions=max_pred,
                mask_prob=cfg.data.mlm_mask_prob, seed=cfg.data.seed)
            return tr, te
        tr, te = get_bert_data(cfg.data.data_dir, vocab_size=vocab,
                               seq_len=seq_len, max_predictions=max_pred,
                               mask_prob=cfg.data.mlm_mask_prob,
                               synthetic=cfg.data.synthetic)
        if bert_cfg and tr["input_ids"].shape[1] > bert_cfg.max_len:
            raise SystemExit(
                f"dataset sequence length {tr['input_ids'].shape[1]} "
                f"exceeds the model's max_len {bert_cfg.max_len}")
        return tr, te
    else:
        raise SystemExit(f"dataset {name!r} not wired into the CLI yet")
    return ({"x": d["train_x"], "y": d["train_y"]},
            {"x": d["test_x"], "y": d["test_y"]})


class TrainRun(NamedTuple):
    """What one CLI invocation built and produced (:func:`run`)."""
    trainer: Any
    state: Any
    summary: dict


def main(argv: list[str] | None = None) -> int:
    run(argv)
    return 0


def run(argv: list[str] | None = None, *, mesh=None) -> TrainRun | None:
    """Everything ``python -m ...cli.train`` does for ``argv``, returning
    the trainer, the final state and the summary to an in-process caller
    (``chip_smoke.py`` trains through here and then serves the very
    parameters it got back). ``None`` for the ``ps`` role, which only
    prints its notice. ``mesh`` replaces the mesh the Trainer would
    build from ``--mesh`` over all devices — the one thing a flag
    cannot say, since a mesh is made of device objects."""
    parser = build_parser()
    args = parser.parse_args(argv)
    if args.eval_only and not args.ckpt_dir:
        # fail fast: everything below (dataset load, mesh, Trainer) can
        # take minutes for the big datasets
        raise SystemExit("--eval_only requires --ckpt_dir")
    for flag, d in (("--export_dir", args.export_dir),
                    ("--export_generator", args.export_generator)):
        if not d:
            continue
        # fail fast on an unwritable export target too — discovering a
        # PermissionError AFTER a multi-hour run wastes the whole run
        try:
            os.makedirs(d, exist_ok=True)
            if not os.access(d, os.W_OK):
                raise PermissionError(d)
        except OSError as e:
            raise SystemExit(f"{flag} is not writable: {e}")
    if args.label_smoothing and args.model not in ("lenet", "resnet20",
                                                   "resnet50"):
        # a silently ignored training knob is worse than an error
        raise SystemExit(
            f"--label_smoothing is wired for the image classifiers "
            f"(lenet/resnet20/resnet50), not model {args.model!r}")
    if args.lm_loss_chunk is not None and not args.model.startswith("gpt"):
        raise SystemExit(
            f"--lm_loss_chunk is a causal-LM knob (gpt/gpt_tiny), not "
            f"for model {args.model!r}")
    # LM-head loss levers make sense only for the models whose loss IS
    # an LM-head xent (causal GPT next-token; the BERT-family MLM heads
    # — including the MoE/pipeline variants, which share Bert's head)
    lm_head_model = args.model.startswith(
        ("gpt", "bert", "moe_bert", "pipe_bert", "pipe_moe"))
    if ((args.lm_loss_impl is not None
         or args.lm_loss_vocab_block is not None)
            and not lm_head_model):
        raise SystemExit(
            f"--lm_loss_impl/--lm_loss_vocab_block configure the LM-head "
            f"cross-entropy (gpt/bert families), not for model "
            f"{args.model!r}")
    if args.token_accuracy_every_n != 1 and not args.model.startswith(
            "gpt"):
        raise SystemExit(
            f"--token_accuracy_every_n is a causal-LM knob (gpt/"
            f"gpt_tiny), not for model {args.model!r}")
    cfg = config_from_args(args)          # reused below for the run
    try:
        # fail fast on flash-lever misuse: levers without --attention
        # flash, or block values the kernel could never tile (it would
        # silently fall back to XLA, hiding the typo for a whole run)
        flash_attention_kwargs(cfg)
        # ... and on LM-loss lever misuse: conflicting impl/chunk/block
        # combinations that a model deep in the run would reject anyway
        lm_loss_settings(cfg)
        # ... and on self-healing misconfiguration: a rollback policy
        # with nothing to roll back to, or a fault spec the injection
        # grammar cannot honor (a silently ignored fault rule would fake
        # chaos coverage for a whole run)
        anomaly_settings(cfg)
        if cfg.fault_spec:
            from ..runtime import faults as faults_mod
            faults_mod.parse_spec(cfg.fault_spec, seed=cfg.seed)
    except ValueError as e:
        raise SystemExit(str(e))
    if args.export_generator and not args.model.startswith("gpt"):
        raise SystemExit(
            f"--export_generator is a causal-LM knob (gpt/gpt_tiny), "
            f"not for model {args.model!r} — only decoder models have "
            "a KV-cache generate path")
    gen_dests = [d for d in vars(args)
                 if d.startswith("gen_")]     # every --gen_* flag
    if not args.export_generator:
        for d in gen_dests:
            if getattr(args, d) != parser.get_default(d):
                raise SystemExit(
                    f"--{d} configures the generator artifact and "
                    "does nothing without --export_generator DIR")
    else:
        # fail fast on knob combinations generate() would reject AFTER
        # the (possibly multi-hour) training run — same rationale as the
        # export-dir writability precheck above
        if ((args.gen_top_k or args.gen_top_p)
                and args.gen_temperature <= 0.0):
            raise SystemExit(
                "--gen_top_k/--gen_top_p shape the sampling "
                "distribution; set --gen_temperature > 0")
        if not 0.0 <= args.gen_top_p <= 1.0:
            raise SystemExit(
                f"--gen_top_p must be in [0, 1], got {args.gen_top_p}")
        if args.gen_top_k < 0:
            raise SystemExit(
                f"--gen_top_k must be >= 0, got {args.gen_top_k}")
        for flag, v in (("--gen_prompt_len", args.gen_prompt_len),
                        ("--gen_max_new", args.gen_max_new),
                        ("--gen_batch", args.gen_batch)):
            if v < 1:
                raise SystemExit(f"{flag} must be >= 1, got {v}")
    for flag, val in (("--moe_experts", args.moe_experts),
                      ("--moe_top_k", args.moe_top_k),
                      ("--moe_capacity_factor", args.moe_capacity_factor),
                      ("--moe_every", args.moe_every),
                      ("--moe_aux_weight", args.moe_aux_weight),
                      ("--moe_router_z_weight", args.moe_router_z_weight),
                      ("--moe_jitter", args.moe_jitter)):
        if val is not None and not (args.model.startswith("moe_")
                            or args.model.startswith("pipe_moe_")):
            raise SystemExit(
                f"{flag} is an MoE routing knob (moe_bert/moe_bert_tiny/"
                f"pipe_moe_bert/pipe_moe_bert_tiny), not for "
                f"model {args.model!r}")

    cluster = None
    if args.ps_hosts or args.worker_hosts:
        cluster = ClusterSpec({
            "ps": parse_hosts(args.ps_hosts),
            WORKER_JOB: parse_hosts(args.worker_hosts) or ["localhost:0"],
        })

    from ..runtime.device import enable_compilation_cache
    from ..runtime.server import Server
    enable_compilation_cache()
    server = Server(cluster, args.job_name, args.task_index,
                    profiler_port=args.profiler_port or None)
    if not server.role.should_run:          # ps branch: notice + exit 0
        server.join()
        return None

    if cfg.obs.debug_nans:
        import jax
        jax.config.update("jax_debug_nans", True)
    from ..models import get_model
    from ..train.trainer import Trainer

    model = get_model(cfg.model, cfg)
    if args.export_generator:
        # the generator prechecks that need the model: fail BEFORE
        # training, not in the post-run export
        ml = getattr(getattr(model, "cfg", None), "max_len", None)
        if ml and args.gen_prompt_len + args.gen_max_new > ml:
            raise SystemExit(
                f"--gen_prompt_len {args.gen_prompt_len} + "
                f"--gen_max_new {args.gen_max_new} exceeds the model's "
                f"max_len {ml}")
        vs = getattr(getattr(model, "cfg", None), "vocab_size", None)
        if vs and args.gen_top_k > vs:
            raise SystemExit(
                f"--gen_top_k {args.gen_top_k} exceeds the model's "
                f"vocab_size {vs}")
    train_arrays, eval_arrays = load_dataset(cfg, model,
                                             eval_only=args.eval_only)
    train_transform = None
    if cfg.data.augment and cfg.data.dataset in CIFAR_DATASETS:
        # CIFAR pad-4-crop + flip is a loader transform (in-memory
        # arrays); the ImageNet recipe lives in the streaming decode
        from ..data.cifar import make_augment_transform
        train_transform = make_augment_transform(cfg.data.seed)
    ctx = server.context
    trainer = Trainer(model, cfg, train_arrays, eval_arrays, mesh=mesh,
                      process_index=ctx.process_index if ctx else 0,
                      num_processes=ctx.num_processes if ctx else 1,
                      train_transform=train_transform)

    if args.eval_only:
        # standalone evaluate-a-checkpoint path: the reference's final
        # test-accuracy pass (SURVEY.md §2.1) without the training run
        if eval_arrays is None:
            raise SystemExit("--eval_only: no eval split for this dataset")
        import jax

        from ..ckpt.checkpoint import _agreed_latest_step
        with trainer:
            # the step choice must agree across processes (broadcast from
            # process 0) exactly like restore_or_init — per-process
            # "latest" can diverge on a lagging shared filesystem
            if args.eval_best:
                if args.eval_step is not None:
                    raise SystemExit(
                        "--eval_best and --eval_step are exclusive")
                # broadcast like the latest-step path: per-process reads
                # of the state file can diverge on a lagging shared fs
                from ..ckpt.checkpoint import _agreed_best_step
                step = _agreed_best_step(trainer.ckpt_manager)
                if step is None:
                    raise SystemExit(
                        "--eval_best: no best checkpoint recorded under "
                        f"{args.ckpt_dir!r} (train with "
                        "--keep_best_metric first)")
            else:
                step = (args.eval_step if args.eval_step is not None
                        else _agreed_latest_step(trainer.ckpt_manager))
            if step is None:
                raise SystemExit(
                    f"--eval_only: no checkpoint under {args.ckpt_dir!r}")
            template = trainer.sync.init(model.init, seed=cfg.seed)
            try:
                state = trainer.ckpt_manager.restore(template, step=step)
            except FileNotFoundError as e:
                raise SystemExit(f"--eval_only: {e}")
            metrics = trainer.evaluate(state)
        import json as _json
        print(_json.dumps({"step": int(jax.device_get(state.step)),
                           **{k: round(float(v), 6)
                              for k, v in metrics.items()}}), flush=True)
        # export-from-checkpoint: the natural serving path (restore,
        # optionally eval, ship the artifact)
        _maybe_export(args, cfg, model, state, ctx)
        return TrainRun(trainer, state, {"eval": metrics})

    with trainer:
        state, summary = trainer.train()

    # the reference's closing print: final test accuracy (SURVEY.md §2.1)
    if "eval" in summary:
        log.info("final eval: %s",
                 {k: round(v, 4) for k, v in summary["eval"].items()})
    log.info("done: step=%d wall=%.1fs steps/sec=%.2f",
             summary["final_step"], summary["wall_time_sec"],
             summary["steps_per_sec"])

    _maybe_export(args, cfg, model, state, ctx)
    return TrainRun(trainer, state, summary)


def _maybe_export(args, cfg, model, state, ctx) -> None:
    """SavedModel-parity export of the trained forward (EMA shadow when
    enabled — the tf export recipe used ema variables) and, for causal
    LMs, the ``--export_generator`` decode artifact. The host gather
    inside the exporters is collective, so every process enters; only
    process 0 writes."""
    if not (args.export_dir or args.export_generator):
        return
    from ..train.optimizers import find_ema_params
    params = (find_ema_params(state.opt_state)
              if cfg.optimizer.ema_decay > 0 else None)
    params = params if params is not None else state.params
    chief = (ctx.process_index if ctx else 0) == 0
    if args.export_dir:
        from ..serving import export_model
        artifact = export_model(
            model, params, state.extras, args.export_dir,
            batch_size=min(8, cfg.data.batch_size))
        if chief:
            log.info("exported servable: %s", artifact)
    if args.export_generator:
        from ..serving import export_generator
        artifact = export_generator(
            model, params, args.export_generator,
            prompt_len=args.gen_prompt_len,
            max_new_tokens=args.gen_max_new,
            batch_size=args.gen_batch,
            temperature=args.gen_temperature,
            top_k=args.gen_top_k, top_p=args.gen_top_p,
            eos_id=args.gen_eos_id, pad_id=args.gen_pad_id,
            ragged=args.gen_ragged,
            weight_quant=args.gen_weight_quant)
        if chief:
            log.info("exported generator: %s", artifact)


if __name__ == "__main__":
    sys.exit(main())
