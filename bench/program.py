"""The system under test, as the benchmark drives it: the trainer's own
entry points (``repro.launch.train``) on the model a configuration file
describes.  Everything the benchmark takes from the program goes through
this module."""

from __future__ import annotations

import math
import os
import sys
from dataclasses import replace
from typing import Any, Dict

import jax
import jax.numpy as jnp

from . import cells

SRC = os.path.join(cells.ROOT, "src")
DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def import_program():
    """Put the checkout's ``src`` on the path (the benchmark runs as a plain
    script from the checkout's root)."""
    if SRC not in sys.path:
        sys.path.insert(0, SRC)
    import repro  # noqa: F401  (fails outside a checkout)


def model_config(config_doc: Dict[str, Any], traffic: Dict[str, Any]):
    """The registry's configuration with every key of the file's ``model``
    applied, and the traffic's operator; refuses a key the program lacks and
    a layer pattern that differs from the registry's."""
    import_program()
    from repro.configs import get_config
    from repro.configs.base import LayerSpec, MoEConfig, SSMConfig

    base = get_config(config_doc["arch"])
    kw = {}
    for key, val in config_doc["model"].items():
        if key == "pattern":
            want = tuple(LayerSpec(**p) for p in val)
            if base.pattern != want:
                raise ValueError(f"pattern {want} is not the registry's {base.pattern}")
            continue
        if not hasattr(base, key):
            raise KeyError(f"the program's config has no key {key!r}")
        if key in ("param_dtype", "compute_dtype", "h_dtype"):
            val = DTYPES[val]
        elif key == "comp_p":
            val = math.inf if val == "inf" else float(val)
        elif key == "moe":
            val = MoEConfig(**val)
        elif key == "ssm":
            val = SSMConfig(**val)
        kw[key] = val
    kw["compression"] = traffic["compression"]
    return replace(base, **kw)


def check_layout(cfg, layout) -> None:
    """The program's parameter tree has the reference's paths, shapes and
    types, leaf for leaf."""
    from repro.models import init_model

    from . import reference as R

    shapes = jax.eval_shape(lambda k: init_model(cfg, k), jax.random.PRNGKey(0))
    got = dict(zip(R.leaf_paths(shapes),
                   [(tuple(s.shape), jnp.dtype(s.dtype).name)
                    for s in jax.tree_util.tree_leaves(shapes)]))
    want = dict(zip(R.leaf_paths(layout),
                    [(tuple(l.shape), jnp.dtype(R.DTYPES[l.dtype]).name)
                     for l in jax.tree_util.tree_leaves(layout, is_leaf=R._is_leaf)]))
    if got != want:
        diff = sorted(set(got.items()) ^ set(want.items()))
        raise ValueError(f"parameter layout differs from the reference's: {diff[:8]}")


def make_mesh(chips: int):
    """``(chips, 1)`` over ``("data", "model")``: each chip one worker."""
    import numpy as np
    from jax.sharding import AxisType, Mesh

    from repro.launch.mesh import make_mesh as program_mesh

    devices = jax.devices()
    if len(devices) == chips:
        return program_mesh((chips, 1), ("data", "model"))
    return Mesh(np.array(devices[:chips]).reshape(chips, 1), ("data", "model"),
                axis_types=(AxisType.Auto, AxisType.Auto))


def trainer(cfg, traffic, mesh):
    """The trainer's optimizer, state shardings and jitted step."""
    from repro.launch.train import (build_train_step, make_optimizer,
                                    train_state_shardings)
    from repro.models import init_model

    if traffic["inner_optimizer"] != "momentum":
        raise ValueError("the comparison reads a momentum optimizer's state")
    opt = make_optimizer(cfg, lr=traffic["lr"], inner="momentum", beta=traffic["beta"])
    n = traffic["workers"]
    params_shape = jax.eval_shape(lambda k: init_model(cfg, k), jax.random.PRNGKey(0))
    opt_shape = jax.eval_shape(lambda p: opt.init(p, n), params_shape)
    p_shard, o_shard = train_state_shardings(cfg, opt, mesh, params_shape, opt_shape)
    step = build_train_step(cfg, opt, mesh)
    init_opt = jax.jit(lambda p: opt.init(p, n), out_shardings=o_shard)
    return opt, p_shard, init_opt, step


def batch_shardings(batch, mesh):
    from jax.sharding import NamedSharding

    from repro.launch.sharding_rules import batch_specs

    return jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s),
                                  batch_specs(batch, mesh))


def momentum(opt_state):
    """The inner optimizer's velocity tree (after one step from zero it is
    the direction the optimizer got)."""
    return opt_state.inner
