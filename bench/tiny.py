"""Cells at test size for the CPU tests: the benchmark's model family cut
to a few thousand parameters, in bfloat16 as the real cells run."""

import copy

import jax
import jax.numpy as jnp

MAMBA = {"n_layers": 2, "d_model": 64, "vocab": 500, "tie_embeddings": True,
         "norm_eps": 1e-5, "pattern": [{"mixer": "mamba", "mlp": "none"}],
         "ssm": {"d_state": 16, "expand": 2, "head_dim": 16, "n_groups": 1,
                 "conv_width": 4, "chunk_size": 32},
         "param_dtype": "bfloat16", "compute_dtype": "bfloat16", "remat": "full",
         "comp_block": 128, "comp_p": "inf", "comp_bucketed": True,
         "h_dtype": "float32"}
ARCH = {"mamba": ("mamba2-130m", MAMBA)}
E2E = [{"name": "tokens_per_s", "unit": "tokens/s"}, {"name": "setup_s", "unit": "s"}]


def spec(family: str, workers: int = 1, batch: int = 2, limits=None, f32: bool = False):
    arch, model = ARCH[family]
    model = copy.deepcopy(model)
    if f32:
        model.update(param_dtype="float32", compute_dtype="float32")
    traffic = {"seq_len": 64, "batch_per_worker": batch, "workers": workers,
               "compression": "diana", "inner_optimizer": "momentum", "lr": 3e-4,
               "beta": 0.9, "pool": 4, "noise": 0.1, "grammars": 1}
    return {"cell": {"name": f"test.{family}", "chips": workers},
            "config_doc": {"arch": arch, "model": model}, "traffic": traffic,
            "limits": limits, "end_to_end": E2E, "per_layer": []}


def state_unchanged(compiled):
    """A step that returns its state unchanged (its loss still computed)."""
    def call(params, opt_state, batch, key):
        copies = jax.tree_util.tree_map(jnp.copy, (params, opt_state))
        _, _, metrics = compiled(*copies, batch, key)
        return params, opt_state, metrics
    return call
