"""Config system: dataclass configs + a flag surface preserving the reference CLI.

The reference configured everything through ``tf.app.flags`` (absl) plus a
``ConfigProto`` (SURVEY.md §5.6). Here the runtime knobs live in plain
dataclasses (no proto dependency), and :func:`add_legacy_flags` /
:func:`cluster_from_flags` reproduce the reference's exact CLI surface
(``--ps_hosts --worker_hosts --job_name --task_index``, SURVEY.md §2.1) on top
of ``argparse`` so existing launch scripts keep working.
"""

from __future__ import annotations

import argparse
import dataclasses
from typing import Any, Mapping, Sequence


@dataclasses.dataclass
class DataConfig:
    """Input pipeline configuration (SURVEY.md §2.1 'Input pipeline')."""

    dataset: str = "mnist"          # mnist | cifar10 | imagenet | bert
    data_dir: str | None = None     # directory with real files; None => synthetic
    batch_size: int = 128           # GLOBAL batch size (split over the data axis)
    shuffle: bool = True
    seed: int = 0
    synthetic: bool = False         # force synthetic data even if data_dir set
    prefetch: int = 2               # host-side prefetch depth
    native: bool = False            # C++ loader (data/native.py) when built;
                                    # falls back to Python when unavailable
    max_per_class: int | None = None  # cap eager folder-tree decode (ImageNet)
    label_offset: int = 0           # TFRecord image shards: added to
                                    # every label (tf-slim ImageNet
                                    # writes 1-indexed labels: pass -1)
    streaming: bool = False         # decode-per-batch thread-pool pipeline
                                    # (data/streaming.py) instead of eager
                                    # whole-split decode — ImageNet scale
    fast_decode: bool = False       # JPEG DCT-domain downscale decode
                                    # (streaming train split; ~1.9x
                                    # decode throughput, pixels deviate
                                    # slightly from the plain decode)
    augment: bool = False           # training augmentation, train split
                                    # only: ImageNet random-resized crop +
                                    # flip (streaming path), CIFAR pad-4
                                    # crop + flip (loader transform)
    # BERT-only knobs
    seq_len: int = 128
    vocab_size: int = 30522
    mlm_mask_prob: float = 0.15


@dataclasses.dataclass
class OptimizerConfig:
    """Base-optimizer knobs (reference: GradientDescent under
    SyncReplicasOptimizer, SURVEY.md §2.1)."""

    name: str = "sgd"               # sgd | momentum | adam | adamw |
                                    # lars | lamb (large-batch recipes) |
                                    # adafactor (factored 2nd moments;
                                    # momentum=0 -> T5 memory-frugal)
    learning_rate: float = 0.5
    momentum: float = 0.9
    weight_decay: float = 0.0
    wd_mask: str = "exclude_1d"     # exclude_1d (standard: no decay on
                                    # biases/LayerNorm scales — any leaf
                                    # with ndim<=1) | all. NOTE: the
                                    # default changed to exclude_1d in
                                    # round 3; pass "all" to reproduce
                                    # older decay-everything runs (no
                                    # recorded artifact used nonzero wd)
    warmup_steps: int = 0
    decay_schedule: str = "constant"  # constant | cosine | linear |
                                      # piecewise | exponential |
                                      # polynomial | natural_exp |
                                      # inverse_time (tf.train family)
    decay_boundaries: tuple[int, ...] = ()  # piecewise: steps where LR drops
    decay_factor: float = 0.1       # piecewise: multiplier at each boundary;
                                    # exponential: decay rate per decay_steps
    decay_steps: int = 0            # exponential: steps per decay_factor
                                    # application (tf.train.exponential_decay
                                    # 'decay_steps'); staircase off.
                                    # polynomial: absolute step where the
                                    # decay bottoms out (falls back to
                                    # total_steps when 0)
    end_learning_rate: float = 0.0  # polynomial AND cosine: floor LR
                                    # (tf.train.polynomial_decay
                                    # 'end_learning_rate' /
                                    # cosine_decay 'alpha' = end/base)
    decay_power: float = 1.0        # polynomial: exponent ('power';
                                    # 1.0 = the linear BERT recipe)
    total_steps: int = 0            # for schedules; 0 => constant
    grad_clip_norm: float = 0.0     # 0 disables
    grad_clip_value: float = 0.0    # elementwise |g| clip
                                    # (tf.clip_by_value; 0 disables;
                                    # composes with the norm clip)
    moment_dtype: str = "float32"   # float32 | bfloat16 — first-moment
                                    # (mu / momentum buffer) storage dtype;
                                    # bf16 halves that HBM traffic slice
    ema_decay: float = 0.0          # > 0 maintains a shadow-param EMA
                                    # (tf.train.ExponentialMovingAverage
                                    # parity); eval uses the shadow
    ema_debias: bool = False        # tf 'num_updates' ramp:
                                    # min(decay, (1+n)/(10+n))


@dataclasses.dataclass
class SyncConfig:
    """Sync-replica semantics — the SyncReplicasOptimizer surface
    (sync_replicas_optimizer.py:142 in the reference stack, per SURVEY.md).

    On TPU the barrier/token protocol is implicit in the single compiled
    step; ``replicas_to_aggregate`` maps onto the size of the data axis and
    ``accum_steps`` provides accumulate-N-then-apply within a replica
    (microbatching), which is the closest TPU-native analogue of gradient
    accumulation on the PS.
    """

    replicas_to_aggregate: int | None = None  # None => data-axis size
    total_num_replicas: int | None = None     # must equal replicas_to_aggregate:
                                              # backup replicas have no TPU
                                              # analogue (hard error otherwise)
    accum_steps: int = 1                      # microbatch accumulation inside the step
    mode: str = "auto"                        # auto (jit+sharding) | shard_map (explicit psum)


@dataclasses.dataclass
class MeshShape:
    """Logical mesh axis sizes. Total must equal the device count in use.

    data: pure data parallel; fsdp: data parallel with sharded params/opt
    state (ZeRO-ish); model: tensor parallel; seq: sequence/context parallel
    (ring attention); expert: MoE expert parallel; pipe: pipeline stages.
    """

    data: int = 1
    fsdp: int = 1
    model: int = 1
    seq: int = 1
    expert: int = 1
    pipe: int = 1

    def total(self) -> int:
        return (self.data * self.fsdp * self.model * self.seq *
                self.expert * self.pipe)

    def as_dict(self) -> dict[str, int]:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class CheckpointConfig:
    """Saver parity (SURVEY.md §3.4/§5.4): chief-writes, max_to_keep ring,
    'checkpoint' state file, restore-or-init."""

    directory: str | None = None
    warm_start: str | None = None   # checkpoint file/dir to initialize
                                    # params from when no checkpoint
                                    # exists in `directory`
                                    # (tf.train.init_from_checkpoint
                                    # parity; resume always wins)
    warm_start_map: str = ""        # 'ckpt_prefix:model_prefix' pairs,
                                    # comma-separated (assignment_map)
    max_to_keep: int = 5
    keep_best_metric: str | None = None  # eval metric tracked for the
                                         # 'best' checkpoint
                                         # (BestExporter parity; needs
                                         # an eval split)
    keep_best_mode: str = "max"          # max (accuracy) | min (loss)
    save_steps: int = 0             # save every N steps (0 disables step-based)
    save_secs: float = 0.0          # save every T seconds (0 disables time-based)
    keep_checkpoint_every_n_hours: float = 0.0
    async_save: bool = False
    sharded: bool = False           # per-process shard files (TF Saver
                                    # sharded=True analogue): each host
                                    # writes only the pieces it owns — no
                                    # cross-host gather on save


@dataclasses.dataclass
class ObservabilityConfig:
    """Metrics/logging parity (SURVEY.md §5.1/§5.5)."""

    log_every_steps: int = 100
    metrics_path: str | None = None   # JSONL sink; None => stdout only
    tb_logdir: str | None = None      # TensorBoard event-file sink
                                      # (utils/tb_events.py, SURVEY §5.5)
    profile_steps: tuple[int, int] | None = None  # (start, stop) step range
    profile_dir: str | None = None
    check_nans: bool = False          # NanTensorHook analogue
    summary_every_steps: int = 0      # scalar summary cadence (0 disables)
    param_histograms_every_steps: int = 0  # weight-histogram cadence
                                           # (tf.summary.histogram
                                           # parity; 0 disables; pulls
                                           # params to host each time)
    debug_checks: bool = False        # checkify float_checks around the step
                                      # (SURVEY.md §5.2); debug-only cost
    debug_nans: bool = False          # jax.config jax_debug_nans flag
    step_timing: bool = False         # per-dispatch device-time records +
                                      # compiled-step cost analysis in the
                                      # metrics JSONL (WorkerCacheLogger
                                      # parity, SURVEY.md §2.4/§5.1);
                                      # blocks the dispatch queue per step
    trace_path: str | None = None     # dump the training-loop trace
                                      # lanes (data-wait / step /
                                      # checkpoint / rollback, obs/
                                      # trace.py) as Perfetto-loadable
                                      # JSON here when train() ends
                                      # (chief only)
    trace_buffer_events: int = 65536  # span ring-buffer bound for the
                                      # trace above (oldest drop first)


@dataclasses.dataclass
class TrainConfig:
    """Top-level config for a training run."""

    model: str = "mlp"
    data: DataConfig = dataclasses.field(default_factory=DataConfig)
    optimizer: OptimizerConfig = dataclasses.field(default_factory=OptimizerConfig)
    sync: SyncConfig = dataclasses.field(default_factory=SyncConfig)
    mesh: MeshShape = dataclasses.field(default_factory=MeshShape)
    checkpoint: CheckpointConfig = dataclasses.field(default_factory=CheckpointConfig)
    obs: ObservabilityConfig = dataclasses.field(default_factory=ObservabilityConfig)
    train_steps: int = 1000
    label_smoothing: float = 0.0     # image classifiers (resnet20/50):
                                     # smooth training targets; eval
                                     # metrics stay unsmoothed
    # MoE model knobs (moe_bert*): None = the model's default. The CLI
    # rejects them for non-MoE models (no silently ignored knobs)
    moe_experts: int | None = None       # experts per MoE layer
    moe_top_k: int | None = None         # routed experts per token
    moe_capacity_factor: float | None = None
    moe_every: int | None = None         # MoE FFN every k-th layer
    moe_aux_weight: float | None = None  # load-balancing loss weight
    moe_router_z_weight: float | None = None   # ST-MoE router z-loss
    moe_jitter: float | None = None      # router noise U[1-j,1+j] (train)
    lm_loss_impl: str | None = None      # LM-head loss strategy for the
                                         # language models (gpt*/bert
                                         # families): full | chunked |
                                         # fused (blockwise vocab scan,
                                         # no [B,S,V] logits in fwd or
                                         # bwd — ops/losses.py). None =
                                         # the model default ("full";
                                         # "chunked" when lm_loss_chunk
                                         # is set — the legacy spelling)
    lm_loss_chunk: int | None = None     # gpt: seq-chunked LM loss (0=full;
                                         # the pre-fused fallback lever)
    lm_loss_vocab_block: int | None = None  # fused: vocab tile (0 = the
                                            # losses.DEFAULT_VOCAB_BLOCK;
                                            # swept by experiments/
                                            # vocab_chain_sweep.py)
    token_accuracy_every_n: int = 1      # gpt: cadence of the per-step
                                         # token_accuracy argmax on the
                                         # full/chunked paths (measured
                                         # 3.2 ms/step at the 30k vocab;
                                         # skipped steps publish -1.0;
                                         # rejected with impl=fused,
                                         # whose accuracy is free)
    eval_every_steps: int = 0        # 0 => eval only at the end
    early_stop_metric: str | None = None  # stop when this eval metric
                                          # stops improving
                                          # (stop_if_no_decrease_hook
                                          # parity; needs
                                          # eval_every_steps)
    early_stop_patience: int = 3     # evals without improvement before
                                     # stopping
    early_stop_mode: str = "max"     # max (accuracy) | min (loss)
    steps_per_loop: int = 1          # steps per device dispatch (lax.scan
                                     # inner loop — TPU-era iterations_per_loop
                                     # semantics; hook cadences must divide)
    max_inflight_steps: int = 0      # cap un-blocked step dispatches in
                                     # flight: block the host every N
                                     # trained steps (0 = let JAX's async
                                     # queue run free — the right default;
                                     # the knob exists as the documented
                                     # mitigation for runtime stacks that
                                     # misbehave under deep dispatch
                                     # queues — the round-4 queued
                                     # INVALID_ARGUMENT, BASELINE.md)
    on_anomaly: str = "halt"         # policy when a step's loss or global
                                     # grad-norm is non-finite (on-device
                                     # detection, observed at the log
                                     # cadence — no per-step host sync):
                                     # halt = stop the run with a summary;
                                     # skip = identity update, keep going;
                                     # rollback = restore the last
                                     # VERIFIED checkpoint and replay
                                     # (needs checkpoint.directory +
                                     # save_steps). Every policy keeps
                                     # non-finite updates out of the state
    max_anomalies: int = 10          # anomaly budget for skip/rollback:
                                     # more anomalous steps than this
                                     # halts the run with a summary (0 =
                                     # halt on the first one)
    fault_spec: str = ""             # deterministic fault injection
                                     # (runtime/faults.py grammar, e.g.
                                     # 'ckpt.write:step=2:raise=OSError;
                                     # loader.next:p=0.01'); empty =
                                     # inert — production paths pay zero
                                     # cost
    seed: int = 0
    num_layers: int = 0              # depth of a model built from a block
                                     # description (models/decoder.py);
                                     # 0 = the model's own
    dtype: str = "float32"           # compute dtype: float32 | bfloat16
    param_dtype: str = "float32"
    bn_stats_dtype: str = "float32"  # BN batch-statistic reduction dtype
                                     # (conv models; running stats stay f32)
    attention_impl: str = "xla"      # xla | flash (pallas kernel; long-seq)
    # flash-kernel tuning levers (attention_impl="flash" only). Left at
    # their defaults the kernel chooses tiles and backward variant from
    # (S, D, dtype) — ops/pallas/flash_attention.flash_schedule; a lever
    # that is set overrides the choice. Sweepable from flags —
    # experiments/flash_sweep.py — so findings are reproducible:
    attention_block_q: int = 0       # fwd Q-tile rows (multiple of 8)
    attention_block_k: int = 0       # fwd K-tile cols (multiple of 128)
    attention_bwd_block: int = 0     # bwd tile for BOTH streamed dims
                                     # (multiple of 128; 0 = the set fwd
                                     # tiles, else the schedule's)
    attention_bwd: str = "auto"      # auto (the schedule's choice) |
                                     # split (two-kernel FA-2 bwd) |
                                     # fused (one kernel: s/p/ds computed
                                     # once for dq+dk+dv — ~29% fewer bwd
                                     # matmul FLOPs, no K/V re-stream;
                                     # runs split where its dq slab
                                     # outgrows VMEM)
    remat: str = "none"              # none | full | dots — jax.checkpoint
                                     # each transformer layer (HBM for
                                     # recompute; long-context enabler)
    prng_impl: str = "threefry2x32"  # | rbg | unsafe_rbg — key impl for
                                     # the training rng stream; rbg uses
                                     # the TPU's native RNG (BERT-base:
                                     # 112→89 ms/step measured; dropout
                                     # masks dominate threefry cost).
                                     # The impl is recorded in
                                     # checkpoints and restored with them

    def replace(self, **kw: Any) -> "TrainConfig":
        return dataclasses.replace(self, **kw)


def flash_attention_kwargs(cfg: TrainConfig) -> dict:
    """Validated flash-kernel kwargs from the ``attention_*`` lever knobs.

    Returns {} when every lever is at its default (any ``attention_impl``
    is fine then); raises ValueError — config validation, before any
    trace — when a lever is set without ``attention_impl="flash"`` (a
    silently ignored knob is worse than an error) or carries a value the
    kernel could never tile (the kernel itself would silently fall back
    to XLA, hiding the typo).
    """
    levers = dict(block_q=cfg.attention_block_q,
                  block_k=cfg.attention_block_k,
                  bwd_block=cfg.attention_bwd_block)
    if cfg.attention_bwd not in ("auto", "split", "fused"):
        raise ValueError(f"attention_bwd must be 'auto', 'split' or "
                         f"'fused', got {cfg.attention_bwd!r}")
    set_levers = {k: v for k, v in levers.items() if v != 0}
    if cfg.attention_bwd != "auto":
        set_levers["bwd_variant"] = cfg.attention_bwd
    if not set_levers:
        return {}
    if cfg.attention_impl != "flash":
        raise ValueError(
            f"attention block/bwd levers ({', '.join(set_levers)}) tune "
            f"the Pallas flash kernel and require attention_impl='flash', "
            f"got {cfg.attention_impl!r}")
    for name, mult in (("block_q", 8), ("block_k", 128),
                       ("bwd_block", 128)):
        v = levers[name]
        if v < 0 or v % mult:
            raise ValueError(
                f"attention_{name}={v} invalid: must be a positive "
                f"multiple of {mult} (Mosaic tile constraint) or 0 for "
                f"the kernel default")
    return set_levers


#: --on_anomaly values anomaly_settings accepts
ANOMALY_POLICIES = ("halt", "skip", "rollback")


def anomaly_settings(cfg: TrainConfig) -> dict:
    """Validated self-healing settings from the ``on_anomaly`` /
    ``max_anomalies`` / ``fault_spec`` knobs — config validation, before
    any trace. Raises ValueError on a policy no path could honor:
    ``rollback`` without the checkpoint cadence it restores from, or a
    negative budget. The fault_spec grammar itself is validated by
    ``runtime.faults.parse_spec`` (jax-free here so config stays
    importable without a backend)."""
    if cfg.on_anomaly not in ANOMALY_POLICIES:
        raise ValueError(f"on_anomaly must be one of {ANOMALY_POLICIES}, "
                         f"got {cfg.on_anomaly!r}")
    if cfg.max_anomalies < 0:
        raise ValueError(
            f"max_anomalies={cfg.max_anomalies} must be >= 0 (the budget "
            "of anomalous steps tolerated before halting)")
    if cfg.on_anomaly == "rollback":
        if not cfg.checkpoint.directory:
            raise ValueError(
                "on_anomaly='rollback' restores the last verified "
                "checkpoint and needs checkpoint.directory (--ckpt_dir)")
        if not (cfg.checkpoint.save_steps or cfg.checkpoint.save_secs):
            raise ValueError(
                "on_anomaly='rollback' needs a checkpoint cadence "
                "(--save_steps or --save_secs): with no checkpoints there "
                "is nothing to roll back to")
    if cfg.obs.check_nans and cfg.on_anomaly != "halt":
        raise ValueError(
            "check_nans (per-step NanHook) pairs with on_anomaly='halt' "
            "only: under skip/rollback an anomalous step's metrics "
            "publish the -1.0 skipped sentinel, so the hook could never "
            "fire (a silently ignored knob is worse than an error)")
    return {"policy": cfg.on_anomaly, "budget": cfg.max_anomalies,
            "fault_spec": cfg.fault_spec}


#: lm_loss_impl values lm_loss_settings accepts (mirrors
#: ops.losses.LM_LOSS_IMPLS without importing jax at config time).
LM_LOSS_IMPLS = ("full", "chunked", "fused")


def lm_loss_settings(cfg: TrainConfig) -> dict:
    """Validated, resolved LM-head loss settings from the ``lm_loss_*``
    / ``token_accuracy_every_n`` knobs.

    Returns ``{"impl", "chunk", "vocab_block", "accuracy_every_n"}``
    with ``None`` defaults resolved (``impl=None`` means "full", or
    "chunked" when ``lm_loss_chunk`` is set — the legacy spelling that
    predates the impl knob). Raises ValueError — config validation,
    before any trace — on values no path could honor or combinations
    that would silently ignore a knob (worse than an error):
    ``chunked`` without a chunk, an explicit non-chunked impl WITH a
    chunk, a vocab block outside ``fused``, or negative sizes.
    """
    impl = cfg.lm_loss_impl
    chunk = cfg.lm_loss_chunk
    block = cfg.lm_loss_vocab_block
    every = cfg.token_accuracy_every_n
    if impl is not None and impl not in LM_LOSS_IMPLS:
        raise ValueError(f"lm_loss_impl must be one of {LM_LOSS_IMPLS}, "
                         f"got {impl!r}")
    if chunk is not None and chunk < 0:
        raise ValueError(f"lm_loss_chunk={chunk} must be >= 0")
    if block is not None and block < 0:
        raise ValueError(f"lm_loss_vocab_block={block} must be >= 0")
    if every < 1:
        raise ValueError(
            f"token_accuracy_every_n={every} must be >= 1 (1 = the "
            "default per-step argmax)")
    if impl == "chunked" and not chunk:
        raise ValueError(
            "lm_loss_impl='chunked' needs lm_loss_chunk > 0 (the chunk "
            "size; it must divide seq_len)")
    if chunk and impl not in (None, "chunked"):
        raise ValueError(
            f"lm_loss_chunk={chunk} conflicts with lm_loss_impl="
            f"{impl!r}: the chunk is the 'chunked' impl's lever (fused "
            "never materializes the logits the chunk recompute bounds; "
            "full materializes them whole)")
    if block and impl != "fused":
        raise ValueError(
            f"lm_loss_vocab_block={block} tunes the fused vocab scan "
            f"and requires lm_loss_impl='fused', got {impl!r}")
    if every != 1 and impl == "fused":
        raise ValueError(
            f"token_accuracy_every_n={every} skips the full/chunked "
            "paths' per-step argmax; the fused path computes accuracy "
            "inside the same vocab scan at no extra cost — drop the "
            "knob (a silently ignored knob is worse than an error)")
    if every != 1 and cfg.sync.accum_steps > 1:
        raise ValueError(
            f"token_accuracy_every_n={every} does not compose with "
            f"accum_steps={cfg.sync.accum_steps}: the loss runs once "
            "per MICROBATCH, so the cadence counter would tick per "
            "microbatch and the microbatch-mean of metrics would "
            "average real accuracies with the -1.0 skipped sentinel "
            "into a number that is neither")
    return {
        "impl": impl or ("chunked" if chunk else "full"),
        "chunk": chunk or 0,
        "vocab_block": block or 0,
        "accuracy_every_n": every,
    }


# ---------------------------------------------------------------------------
# Legacy CLI surface (reference parity)
# ---------------------------------------------------------------------------

def add_legacy_flags(parser: argparse.ArgumentParser) -> None:
    """Install the reference's exact distributed flags (SURVEY.md §2.1).

    ``--ps_hosts``/``--worker_hosts`` are comma-separated host:port lists;
    ``--job_name`` is ``ps`` or ``worker``; ``--task_index`` the task id.
    On TPU the PS role does not exist — see
    :func:`distributed_tensorflow_example_tpu.cluster.resolve_legacy_role`.
    """
    parser.add_argument("--ps_hosts", type=str, default="",
                        help="comma-separated ps host:port list (legacy; no "
                             "PS role on TPU — accepted and mapped away)")
    parser.add_argument("--worker_hosts", type=str, default="",
                        help="comma-separated worker host:port list (legacy)")
    parser.add_argument("--job_name", type=str, default="worker",
                        choices=["ps", "worker"],
                        help="legacy job name; 'ps' exits 0 with a notice")
    parser.add_argument("--task_index", type=int, default=0,
                        help="legacy task index; maps to the JAX process index")


def parse_hosts(csv: str) -> list[str]:
    return [h.strip() for h in csv.split(",") if h.strip()]
