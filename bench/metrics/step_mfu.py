"""Model FLOP/s utilisation of the traced window: the forward and backward
operations required per token (``bench/flops.py``) times tokens per second,
over chips times the bf16 peak, in %."""


def read(ctx):
    tr, peaks = ctx["trace"], ctx["peaks"]
    if not tr or not peaks or not tr["steps"]:
        return None
    tokens_per_s = tr["steps"] * ctx["tokens_per_step"] / tr["window_s"]
    return (100.0 * ctx["train_flops_per_token"] * tokens_per_s
            / (ctx["chips"] * peaks["bf16_flops_per_s"]))
