"""A layer kind for the tests of the lookup: one square projection, with
a term for the loss that depends on the batch as a whole (the square of the
output's mean), and an initialiser of its own."""

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
