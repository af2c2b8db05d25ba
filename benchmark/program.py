"""The one place the benchmark touches the program's set-up surface:
what both kinds need to build the registry's model the way
``cli.train.run`` does. The benchmark imports from the program only what
it measures."""

from __future__ import annotations


def train_config(env, extra_flags=()):
    """The TrainConfig ``cli.train`` would build from the configuration's
    flags, with what no flag can say applied on top."""
    from distributed_tensorflow_example_tpu.cli import train as cli_train
    from distributed_tensorflow_example_tpu.runtime.device import (
        enable_compilation_cache)

    # the program's own seed stays fixed: its programs hold it as a
    # constant, and the run's seed reaches it through the data and weights
    argv = [*env.pick(env.config, "flags"), *extra_flags, "--seed", "0"]
    cfg = cli_train.config_from_args(
        cli_train.build_parser().parse_args(argv))
    for path, value in env.pick(env.config, "train_config").items():
        node, *rest = path.split(".")
        target = cfg
        while rest:
            target, node = getattr(target, node), rest.pop(0)
        setattr(target, node, value)
    enable_compilation_cache()
    return cfg


def build_model(env, cfg):
    """The registry's model for ``cfg``, the reference's view of the same
    configuration, and the seeded-weights spec both share."""
    from distributed_tensorflow_example_tpu.models import get_model

    model = get_model(cfg.model, cfg)
    model.cfg.dropout = float(env.config["dropout"]["rate"])
    ref = env.manifest.reference(env.config)
    ref_cfg = (env.pick(env.config, "sizes") if env.rehearse
               else env.config)
    return model, ref, ref_cfg, ref.param_spec(ref_cfg)


def check_tree(program_params, params) -> None:
    """The reference's parameter tree must be the program's, leaf for leaf."""
    import jax

    from benchmark import refmath

    def shapes(t):
        return refmath.flatten(jax.tree_util.tree_map(
            lambda x: (tuple(x.shape), str(x.dtype)), t))

    want, got = shapes(program_params), shapes(params)
    if want != got:
        diff = sorted(set(want.items()) ^ set(got.items()))[:6]
        raise RuntimeError(f"the reference's parameter tree is not the "
                           f"program's: {diff}")
