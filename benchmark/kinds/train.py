"""Traffic kind ``train``: the program's Trainer over seeded data.

The run builds ONE Trainer (the body of ``cli.train.run``: parser ->
TrainConfig -> registry model -> Trainer), gives its state the seeded
weights, and lets ``Trainer.train()`` run; a hook of the benchmark's own
reads the first steps for the output check, opens the measured window
after the warm-up steps and closes it. The very step object and state
the check read are the ones the window drives.
"""

from __future__ import annotations

import functools
import time

import numpy as np

CHECK_STEPS = 3     # steps the plain reference follows
INFLIGHT = 2        # steps the host may run ahead of the device


class WindowHook:
    """Reads the check steps, then times whole steps between two syncs."""

    every_steps = 0

    def __init__(self, env, spec, b1: float):
        self.env, self.spec, self.b1 = env, spec, b1
        self.losses: list[float] = []
        self.grad_norms = self.delta_norms = None
        self.t_begin = self.t_first = self.t_open = self.t_close = None
        self.steps = 0
        self.done_times: list[tuple[int, float]] = []
        self._markers: list = []
        self._open_step = None
        self._tracing = False
        self.t_first_open = None
        self.snap_open = self.snap_close = None

    # ---- Hook protocol ------------------------------------------------
    def begin(self, trainer):
        self.t_begin = time.perf_counter()

    def end(self, trainer):
        pass

    def wants_metrics(self, step: int) -> bool:
        return step <= CHECK_STEPS

    def after_step(self, trainer, step, metrics):
        import jax
        env = self.env
        if step <= CHECK_STEPS:
            self.losses.append(float(metrics["loss"]))
            if step == 1:
                self.t_first = time.perf_counter()
                env.mark("first step")
                self.grad_norms = _first_grad_norms(trainer.state, self.b1)
            if step == CHECK_STEPS:
                self.delta_norms = _delta_norms(trainer.state.params,
                                                self.spec, env.seed)
                env.mark("check steps read")
            return False
        if step < env.warm_steps:
            return False
        if step == env.warm_steps:
            jax.block_until_ready(trainer.state.params)
            env.mark("warm-up done, window opens")
            self._open(trainer, step)
            if env.trace:
                env.start_trace()
                self._tracing = True
            return False
        # bound the host's run-ahead without draining the device: wait
        # for the step dispatched INFLIGHT steps ago
        self._markers.append((step, trainer.state.step + 0))
        if len(self._markers) > INFLIGHT:
            s, m = self._markers.pop(0)
            jax.block_until_ready(m)
            self.done_times.append((s, time.perf_counter()))
        now = time.perf_counter()
        if self._tracing and now - self.t_open >= env.trace_seconds:
            jax.block_until_ready(trainer.state.params)
            env.stop_trace()
            self._tracing = False
            self._open(trainer, step)       # the rates start over
            return False
        if now - self.t_open >= env.window_seconds:
            jax.block_until_ready(trainer.state.params)
            self.t_close = time.perf_counter()
            self.steps = step - self._open_step
            self.snap_close = trainer.registry.snapshot()
            return True
        return False

    def _open(self, trainer, step):
        self._markers.clear()
        self.done_times.clear()
        self._open_step = step
        self.snap_open = trainer.registry.snapshot()
        self.t_open = time.perf_counter()
        if self.t_first_open is None:
            self.t_first_open = self.t_open


def _adam_mu(opt_state):
    """The first-moment tree inside an optax chain's state."""
    stack = [opt_state]
    while stack:
        node = stack.pop()
        if hasattr(node, "mu") and hasattr(node, "nu"):
            return node.mu
        if isinstance(node, (tuple, list)):
            stack.extend(node)
    raise ValueError("no Adam moments in the optimizer state")


def _leaf_norms(tree) -> dict:
    import jax
    import jax.numpy as jnp

    from benchmark import refmath
    norms = jax.jit(lambda t: jax.tree_util.tree_map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))),
        t))(tree)
    return refmath.flatten(jax.device_get(norms))


def _first_grad_norms(state, b1: float) -> dict:
    """Per-leaf norms of the first gradient AS THE OPTIMIZER GOT IT: after
    one Adam step from zero moments, mu = (1 - b1) * g."""
    return {k: float(v) / (1.0 - b1)
            for k, v in _leaf_norms(_adam_mu(state.opt_state)).items()}


def _delta_norms(params, spec, seed) -> dict:
    import jax
    import jax.numpy as jnp

    from benchmark import weights
    p0 = weights.make_params(spec, seed)
    return {k: float(v) for k, v in _leaf_norms(
        jax.tree_util.tree_map(jnp.subtract, params, p0)).items()}


#: a leaf whose reference gradient is under this share of the median
#: leaf's is all but zero (the key bias: softmax ignores a shift of every
#: score of a query). Adam turns its rounding noise into full-size steps,
#: in the reference as in the program, so its change is not compared.
DEAD_GRADIENT = 1e-3


def worst_gap(got: dict, want: dict, skip=()) -> tuple[float, str]:
    """The worst leaf's gap between two norms (not the norm of a
    difference), against the reference's norm of that leaf or of the
    median leaf, whichever is larger."""
    floor = float(np.median(list(want.values())))
    worst, where = 0.0, ""
    for k, w in want.items():
        if k in skip:
            continue
        gap = float(abs(got[k] - w) / max(w, floor))
        if gap > worst:
            worst, where = gap, k
    return worst, where


def compare(program: dict, reference: dict) -> dict:
    """The numbers the check compares (see PERF.md for their limits)."""
    loss = max(abs(p - r) / abs(r) for p, r in
               zip(program["losses"], reference["losses"]))
    ref_grads = reference["grad_norms"]
    grad, grad_leaf = worst_gap(program["grad_norms"], ref_grads)
    floor = DEAD_GRADIENT * float(np.median(list(ref_grads.values())))
    dead = {k for k, g in ref_grads.items() if g < floor}
    delta, delta_leaf = worst_gap(program["delta_norms"],
                                  reference["delta_norms"], skip=dead)
    return {"loss_rel_gap": float(loss), "grad_norm_gap": grad,
            "delta_norm_gap": delta,
            "_where": {"grad": grad_leaf, "delta": delta_leaf,
                       "dead_gradient_leaves": len(dead)}}


def build(env):
    """The Trainer with seeded weights and seeded data, the hook, and the
    reference's view of the same configuration."""
    import jax
    import jax.numpy as jnp

    from benchmark import datagen, program, refmath, weights
    from distributed_tensorflow_example_tpu.train.trainer import Trainer

    traffic = env.traffic
    cfg = program.train_config(env, [
        *env.pick(traffic, "flags"), "--train_steps", str(10 ** 9),
        "--log_every_steps", "0"])
    env.mark("imports and flags")
    model, ref, ref_cfg, spec = program.build_model(env, cfg)
    data = datagen.train_arrays(env.pick(traffic, "data"),
                                ref_cfg["vocab_size"], env.seed)
    env.mark("seeded data")
    hook = WindowHook(env, spec, traffic["optimizer"]["b1"])
    trainer = Trainer(model, cfg, data, None, hooks=[hook])
    env.mark("trainer built")
    program.check_tree(jax.eval_shape(model.init, jax.random.key(0)),
                       jax.eval_shape(lambda: weights.build(
                           spec, weights.seed_key(env.seed))))
    # the program's own sharded init (SyncReplicas.init) over a zero tree,
    # then the seeded weights in their place. The seed reaches the device
    # as an argument, never as a constant of a compiled program: a program
    # that held it would miss the compilation cache on every new seed
    state = trainer.sync.init(
        lambda _rng: jax.tree_util.tree_map(
            lambda leaf: jnp.zeros(leaf[0], jnp.float32),
            refmath.unflatten(spec), is_leaf=lambda x: isinstance(x, tuple)),
        seed=0, prng_impl=cfg.prng_impl)
    params = jax.device_put(
        weights.make_params(spec, env.seed),
        jax.tree_util.tree_map(lambda x: x.sharding, state.params))
    trainer.state = state.replace(params=params)
    del state, params
    jax.block_until_ready(trainer.state.params)
    env.mark("state with the seeded weights")
    return trainer, hook, ref, ref_cfg, spec


def run(env) -> dict:
    import jax
    import jax.numpy as jnp

    from benchmark import refmath, stats, weights

    traffic = env.traffic
    data = env.pick(traffic, "data")
    trainer, hook, ref, ref_cfg, spec = build(env)
    env.break_program(trainer=trainer)      # tests only: a no-op in a run
    with trainer:
        trainer.train()
    if hook.t_close is None:
        raise RuntimeError("the window never closed")
    tokens_per_step = data["batch_size"] * data["seq_len"]
    rate = stats.whole_step_rate(tokens_per_step, hook.steps,
                                 hook.t_open, hook.t_close)
    peak = env.memory_peak_bytes()          # before the reference runs
    check_loader = trainer._loader(0)       # the window's own feed, again
    batches = [next(check_loader) for _ in range(CHECK_STEPS)]
    if hasattr(check_loader, "close"):
        check_loader.close()
    wait = {k: hook.snap_close["train_data_wait_seconds"][k]
            - hook.snap_open["train_data_wait_seconds"][k]
            for k in ("sum", "count")}
    done = hook.done_times
    step_ms = [1e3 * (b[1] - a[1]) for a, b in zip(done, done[1:])]
    trainer.state = None
    trainer.close()
    del trainer

    # ---- the output check: the plain reference follows the same steps --
    t0 = time.perf_counter()
    params = weights.make_params(spec, env.seed)
    reference = refmath.train_reference(
        functools.partial(ref.loss_sums, ref_cfg), params,
        [{k: jnp.asarray(v) for k, v in b.items()} for b in batches],
        block_rows=env.pick(traffic, "reference_block_rows"),
        optimizer={k: traffic["optimizer"][k] for k in
                   ("lr", "b1", "b2", "eps", "weight_decay")})
    program = {"losses": hook.losses, "grad_norms": hook.grad_norms,
               "delta_norms": hook.delta_norms}
    compared = compare(program, reference)
    where = compared.pop("_where")
    env.note(f"check: reference {time.perf_counter() - t0:.1f}s; losses "
             f"program {hook.losses} reference {reference['losses']}; "
             f"worst leaves {where}")
    return {
        "attempted": hook.steps, "failed": 0,
        "compared": compared,
        "memory_peak_bytes": peak,
        "setup_s": hook.t_first_open - env.t_process,
        "counts": {"steps": hook.steps, "tokens_per_step": tokens_per_step},
        "record": {"step_ms": step_ms, "first_step_s": hook.t_first
                   - hook.t_begin, "window_s": hook.t_close - hook.t_open,
                   "data_wait_s": wait["sum"]},
        "values": {"train_tokens_per_s": rate},
        "ctx": {
            "tokens_per_s": rate, "steps": hook.steps,
            "tokens_per_step": tokens_per_step,
            "window_s": hook.t_close - hook.t_open,
            "values": {
                "first_step_s": hook.t_first - hook.t_begin,
                "data_wait_ms": 1e3 * wait["sum"] / max(1, wait["count"]),
                "step_ms_p50": stats.percentile(step_ms, 50),
            },
            "flops_per_token": ref.train_flops_per_token(
                ref_cfg, data["seq_len"],
                max_predictions=data.get("max_predictions", 0)),
            "data": data, "ref_cfg": ref_cfg, "family": ref.FAMILY,
            "memory_peak_bytes": peak,
        },
    }


def control(env) -> dict:
    """The control: the reference in the program's place, computed in fp8
    (the precision below the bf16 the configuration states), held to the
    float32 reference on the same seeded weights and rows at the cell's
    own size. It has to exceed a limit."""
    import jax.numpy as jnp

    from benchmark import datagen, refmath, weights

    traffic = env.traffic
    data = env.pick(traffic, "data")
    ref = env.manifest.reference(env.config)
    ref_cfg = env.pick(env.config, "sizes") if env.rehearse else env.config
    arrays = datagen.train_arrays(data, ref_cfg["vocab_size"], env.seed)
    b = data["batch_size"]
    batches = [{k: jnp.asarray(v[i * b:(i + 1) * b])
                for k, v in arrays.items()} for i in range(CHECK_STEPS)]
    kw = dict(block_rows=env.pick(traffic, "reference_block_rows"),
              optimizer={k: traffic["optimizer"][k] for k in
                         ("lr", "b1", "b2", "eps", "weight_decay")})
    loss_sums = functools.partial(ref.loss_sums, ref_cfg)
    spec = ref.param_spec(ref_cfg)
    sound = refmath.train_reference(
        loss_sums, weights.make_params(spec, env.seed), batches, **kw)
    low = refmath.train_reference(
        loss_sums, weights.make_params(spec, env.seed), batches,
        precision="fp8", **kw)
    compared = compare(low, sound)
    compared.pop("_where")
    return compared
