"""A dense SwiGLU feed-forward layer for the tests of the yardstick's
lookup: its file defines no counts, so ``bench/flops.py`` keeps its
built-in count of ``dense``."""

import math

import jax


def layout(model, stacked) -> dict:
    d, f, pdt = model["d_model"], model["d_ff"], model["param_dtype"]
    return {"gate": stacked((d, f), pdt, "normal", 1 / math.sqrt(d)),
            "up": stacked((d, f), pdt, "normal", 1 / math.sqrt(d)),
            "down": stacked((f, d), pdt, "normal", 1 / math.sqrt(f))}


def apply(p, x, model, dot):
    h = jax.nn.silu(dot("bsd,df->bsf", x, p["gate"])) * dot("bsd,df->bsf", x, p["up"])
    return dot("bsf,fd->bsd", h, p["down"]), 0
