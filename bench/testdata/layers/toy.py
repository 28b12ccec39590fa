"""A layer kind for the tests of the lookup: one square projection, with
a term for the loss that depends on the batch as a whole (the square of the
output's mean), an initialiser of its own, and its own counts for the
yardstick (``bench/flops.py``)."""

import jax
import jax.numpy as jnp

from bench.reference import F32

AUX_WEIGHT = 0.5
WHOLE_BATCH = True


def _toy_uniform(key, shape):
    return jax.random.uniform(key, shape, F32, -0.1, 0.1)


INITS = {"toy_uniform": _toy_uniform}


def layout(model, stacked) -> dict:
    d = model["d_model"]
    return {"w": stacked((d, d), model["param_dtype"], "toy_uniform")}


def apply(p, x, model, dot):
    y = dot("bsd,de->bse", x, p["w"])
    return y, AUX_WEIGHT * jnp.square(jnp.mean(y))


def matmul_params(model) -> int:
    return model["d_model"] ** 2


def sequence_flops(model, seq_len: int) -> int:
    """The output's mean: one addition per channel and token."""
    return model["d_model"]
