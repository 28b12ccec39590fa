"""Plain float32 reference of the benchmark's models and of DIANA training.

Written from the published descriptions and independent of the program
under test: it imports nothing from ``src/`` and takes nothing the program
made.  The weights it starts from come from :func:`make_params`, the
benchmark's own generator, regenerated from the seed.

* Mamba-2 (arXiv:2405.21060): in-projection, causal depthwise convolution,
  the SSD scan by the paper's minimal chunked listing, gated RMSNorm,
  out-projection.
* DIANA (arXiv:1901.09269, Algorithm 1) with block ternary p=inf
  quantization (Def. 2) per leaf, the memory rate alpha = alpha_inf(B) / 2
  (Lemma 1, Corollary 1), and heavy-ball momentum on the served direction.

Storage follows the configuration: parameters are held in its
``param_dtype`` between steps, memories and momentum in float32.  All
arithmetic is float32; every matrix product goes through :func:`make_dot`,
which in ``"f32"`` runs at HIGHEST precision.  ``"bf16"`` and ``"fp8"``
(float8 e4m3 with a per-tensor scale) round the operands of every product,
forward and backward, to that type first: ``"fp8"`` is the control that the
comparison has to refuse.
"""

from __future__ import annotations

import math
from typing import Dict, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
VOCAB_PAD = 4096          # the embedding table is padded to a multiple of this
CE_CHUNK = 512            # positions per chunk of the loss
SSD_CHUNK = 128           # the reference's own SSD chunk (any size is exact)

ROUNDING = {"f32": None, "bf16": jnp.bfloat16, "fp8": jnp.float8_e4m3fn}
DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def _round_to(x, dtype):
    """``x`` rounded to ``dtype``; float8 with a per-tensor scale that maps
    the largest magnitude to the type's largest (as float8 training does)."""
    if dtype == jnp.float8_e4m3fn:
        amax = jnp.max(jnp.abs(x))
        scale = jnp.where(amax > 0, 448.0 / amax, 1.0)
        return (x * scale).astype(dtype).astype(F32) / scale
    return x.astype(dtype).astype(F32)


def _rounding_pair(dtype):
    """Two identity maps for the operands and the result of a product: the
    first rounds its value going forward, the second rounds the cotangent
    coming back, so that both passes multiply rounded operands."""

    @jax.custom_vjp
    def fwd_round(x):
        return _round_to(x, dtype)

    fwd_round.defvjp(lambda x: (_round_to(x, dtype), None), lambda _, g: (g,))

    @jax.custom_vjp
    def bwd_round(y):
        return y

    bwd_round.defvjp(lambda y: (y, None), lambda _, g: (_round_to(g, dtype),))
    return fwd_round, bwd_round


def make_dot(precision: str):
    """``dot(spec, *operands)``: an einsum in float32 at HIGHEST precision.
    Below ``"f32"`` every operand of the forward product and of the two
    backward products is first rounded to ``precision``."""
    rnd = ROUNDING[precision]
    if rnd is None:
        return lambda spec, *ops: jnp.einsum(spec, *ops, precision=HIGHEST,
                                             preferred_element_type=F32)
    fwd_round, bwd_round = _rounding_pair(rnd)

    def dot(spec, *ops):
        return bwd_round(jnp.einsum(spec, *[fwd_round(o) for o in ops],
                                    precision=HIGHEST, preferred_element_type=F32))

    return dot


def seed_key(seed: int):
    """A PRNG key for any whole seed: the low 32 bits seed it, the high bits
    are folded in (a plain PRNGKey keeps only the low 32)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**32), seed // 2**32)


# ---------------------------------------------------------------------------
# Parameter layout and the benchmark's weight generator
# ---------------------------------------------------------------------------

class Leaf(NamedTuple):
    shape: tuple
    dtype: str
    init: str          # normal | ones | zeros | a_log | dt_bias
    scale: float = 1.0


def padded_vocab(model) -> int:
    return -(-model["vocab"] // VOCAB_PAD) * VOCAB_PAD


def _dims(model):
    d = model["d_model"]
    ssm = model["ssm"]
    d_in = ssm["expand"] * d
    return dict(d=d, d_in=d_in, h=d_in // ssm["head_dim"], p=ssm["head_dim"],
                n=ssm["d_state"], g=ssm["n_groups"], w=ssm["conv_width"])


def param_layout(model) -> dict:
    """The parameter tree as the program lays it out: names, shapes, types
    and the generator's initialisation of each leaf."""
    pdt = model["param_dtype"]
    L = model["n_layers"] // len(model["pattern"])
    d = model["d_model"]
    out_scale = 1.0 / math.sqrt(2 * model["n_layers"])
    tree = {"embed": Leaf((padded_vocab(model), d), pdt, "normal", 0.02),
            "final_norm": {"scale": Leaf((d,), pdt, "ones")}}
    if not model["tie_embeddings"]:
        tree["lm_head"] = Leaf((d, padded_vocab(model)), pdt, "normal", 0.02)

    def stacked(shape, dtype, init, scale=1.0):
        return Leaf((L,) + tuple(shape), dtype, init, scale)

    block = {}
    for i, spec in enumerate(model["pattern"]):
        if (spec["mixer"], spec["mlp"]) != ("mamba", "none"):
            raise ValueError(f"the reference has Mamba-2 layers only, not {spec}")
        m = _dims(model)
        conv_ch = m["d_in"] + 2 * m["g"] * m["n"]
        d_proj = 2 * m["d_in"] + 2 * m["g"] * m["n"] + m["h"]
        block[f"layer{i}"] = {
            "norm1": {"scale": stacked((d,), pdt, "ones")},
            "mixer": {
                "in_proj": stacked((d, d_proj), pdt, "normal", 1 / math.sqrt(d)),
                "conv_w": stacked((m["w"], conv_ch), pdt, "normal", 1 / math.sqrt(m["w"])),
                "conv_b": stacked((conv_ch,), pdt, "zeros"),
                "dt_bias": stacked((m["h"],), "float32", "dt_bias"),
                "A_log": stacked((m["h"],), "float32", "a_log"),
                "D": stacked((m["h"],), "float32", "ones"),
                "norm_scale": stacked((m["d_in"],), pdt, "ones"),
                "out_proj": stacked((m["d_in"], d), pdt, "normal",
                                    out_scale / math.sqrt(m["d_in"])),
            },
        }
    tree["blocks"] = block
    return tree


def _is_leaf(x) -> bool:
    return isinstance(x, Leaf)


def leaf_paths(tree) -> List[str]:
    """'/'-joined key paths of a dict tree, in ``jax.tree_util`` order."""
    flat = jax.tree_util.tree_flatten_with_path(tree, is_leaf=_is_leaf)[0]
    return ["/".join(str(getattr(k, "key", k)) for k in path) for path, _ in flat]


def _init_leaf(spec: Leaf, key):
    dt = DTYPES[spec.dtype]
    if spec.init == "normal":
        x = jax.random.normal(key, spec.shape, F32) * spec.scale
    elif spec.init == "ones":
        x = jnp.ones(spec.shape, F32)
    elif spec.init == "zeros":
        x = jnp.zeros(spec.shape, F32)
    elif spec.init == "a_log":            # A uniform in [1, 16] (Mamba-2)
        x = jnp.log(jax.random.uniform(key, spec.shape, F32, 1.0, 16.0))
    elif spec.init == "dt_bias":          # softplus^-1 of dt, log-uniform [1e-3, 1e-1]
        dt_ = jnp.exp(jax.random.uniform(key, spec.shape, F32,
                                         math.log(1e-3), math.log(1e-1)))
        x = dt_ + jnp.log(-jnp.expm1(-dt_))
    else:
        raise ValueError(spec.init)
    return x.astype(dt)


def _builder(model):
    layout = param_layout(model)
    leaves, treedef = jax.tree_util.tree_flatten(layout, is_leaf=_is_leaf)

    def build(key):
        keys = jax.random.split(key, len(leaves))
        return jax.tree_util.tree_unflatten(
            treedef, [_init_leaf(s, k) for s, k in zip(leaves, keys)])

    return build


def _weights_key(seed: int):
    return jax.random.fold_in(seed_key(seed), 0x3E16)


def make_params(model, seed: int):
    """The benchmark's weights: every leaf from ``seed``, in one jitted call
    on the default device, in the type it is served in.  The program and the
    reference both take them from this one program: on a TPU another
    program that draws the same numbers can round them differently."""
    return jax.jit(_builder(model))(_weights_key(seed))


def host_change_norms(new, old) -> Dict[str, float]:
    """Per-leaf float32 norms of ``new - old``, both host copies."""
    out = {}
    for k, a, b in zip(leaf_paths(new), jax.tree_util.tree_leaves(new),
                       jax.tree_util.tree_leaves(old)):
        d = np.asarray(a, np.float32) - np.asarray(b, np.float32)
        out[k] = float(np.sqrt(np.sum(np.square(d, dtype=np.float64))))
    return out


# ---------------------------------------------------------------------------
# The models
# ---------------------------------------------------------------------------

def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def segsum(a):
    """(..., T) -> (..., T, T): entry [i, j] is a[j+1] + ... + a[i] for
    j <= i, and -inf above the diagonal (the stable form of the listing)."""
    t = a.shape[-1]
    x = jnp.broadcast_to(a[..., :, None], a.shape + (t,))
    x = jnp.where(jnp.tril(jnp.ones((t, t), bool), -1), x, 0.0)
    cs = jnp.cumsum(x, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((t, t), bool)), cs, -jnp.inf)


def ssd(x, a, b, c, chunk, dot):
    """Mamba-2's SSD, the paper's minimal chunked listing.

    x: (B, L, H, P) inputs already times dt; a: (B, L, H) log-decays (A dt);
    b, c: (B, L, G, N), head h reads group h // (H / G).  Returns (B, L, H, P).
    """
    bsz, l, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    r = h // g
    nc = l // chunk
    x = x.reshape(bsz, nc, chunk, h, p)
    b = b.reshape(bsz, nc, chunk, g, n)
    c = c.reshape(bsz, nc, chunk, g, n)
    a = jnp.moveaxis(a.reshape(bsz, nc, chunk, h), 3, 1)          # (B,H,C,Q)
    a_cs = jnp.cumsum(a, axis=-1)

    # within each chunk: (C B^T o L) X
    lmat = jnp.exp(segsum(a))                                     # (B,H,C,Q,Q)
    cb = dot("bclgn,bcsgn->bcgls", c, b)                          # (B,C,G,Q,Q)
    cb = jnp.repeat(cb, r, axis=2) * jnp.moveaxis(lmat, 1, 2)     # (B,C,H,Q,Q)
    y_diag = dot("bchls,bcshp->bclhp", cb, x)

    # each chunk's contribution to the state at its end
    decay_states = jnp.exp(a_cs[..., -1:] - a_cs)                 # (B,H,C,Q)
    bh = jnp.repeat(b, r, axis=3) * jnp.moveaxis(decay_states, 1, 3)[..., None]
    states = dot("bclhn,bclhp->bchpn", bh, x)                     # (B,C,H,P,N)

    # the state entering each chunk
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], axis=1)
    a_tot = jnp.pad(a_cs[..., -1], ((0, 0), (0, 0), (1, 0)))      # (B,H,C+1)
    decay_chunk = jnp.exp(segsum(a_tot))                          # (B,H,C+1,C+1)
    states = jnp.einsum("bhzc,bchpn->bzhpn", decay_chunk, states,
                        precision=HIGHEST)[:, :-1]

    # what the entering state adds to each position
    ch = jnp.repeat(c, r, axis=3) * jnp.moveaxis(jnp.exp(a_cs), 1, 3)[..., None]
    y_off = dot("bclhn,bchpn->bclhp", ch, states)
    return (y_diag + y_off).reshape(bsz, l, h, p)


def mamba_mixer(p, x, model, dot):
    m = _dims(model)
    d_in, h, hp, n, g, w = m["d_in"], m["h"], m["p"], m["n"], m["g"], m["w"]
    bsz, s, _ = x.shape
    proj = dot("bsd,de->bse", x, p["in_proj"])
    z, xr, braw, craw, dt = jnp.split(
        proj, [d_in, 2 * d_in, 2 * d_in + g * n, 2 * d_in + 2 * g * n], axis=-1)
    u = jnp.concatenate([xr, braw, craw], axis=-1)
    up = jnp.pad(u, ((0, 0), (w - 1, 0), (0, 0)))
    conv = sum(up[:, i:i + s] * p["conv_w"][i] for i in range(w)) + p["conv_b"]
    u = jax.nn.silu(conv)
    xr, braw, craw = jnp.split(u, [d_in, d_in + g * n], axis=-1)
    xs = xr.reshape(bsz, s, h, hp)
    dt = jax.nn.softplus(dt + p["dt_bias"])                       # (B,S,H)
    a = -jnp.exp(p["A_log"])                                      # (H,)
    y = ssd(xs * dt[..., None], a * dt, braw.reshape(bsz, s, g, n),
            craw.reshape(bsz, s, g, n), min(SSD_CHUNK, s), dot)
    y = (y + p["D"][:, None] * xs).reshape(bsz, s, d_in)
    y = rms_norm(y * jax.nn.silu(z), p["norm_scale"], model["norm_eps"])
    return dot("bse,ed->bsd", y, p["out_proj"])


def _block(x, bp, model, dot):
    for i in range(len(model["pattern"])):
        lp = bp[f"layer{i}"]
        hx = rms_norm(x, lp["norm1"]["scale"], model["norm_eps"])
        x = x + mamba_mixer(lp["mixer"], hx, model, dot)
    return x


def loss_terms(params, tokens, labels, model, precision="f32"):
    """The sum of next-token cross-entropies over every position.  The
    softmax runs over the padded vocabulary, as the program's head does.
    ``params`` may be of any float type; all math is float32."""
    dot = make_dot(precision)
    p = jax.tree_util.tree_map(lambda a: a.astype(F32), params)

    def body(x, bp):
        return jax.checkpoint(lambda x_, bp_: _block(x_, bp_, model, dot))(x, bp), None

    x, _ = jax.lax.scan(body, p["embed"][tokens], p["blocks"])
    x = rms_norm(x, p["final_norm"]["scale"], model["norm_eps"])
    head = p["embed"] if model["tie_embeddings"] else p["lm_head"].T   # (Vp, D)
    bsz, s, d = x.shape
    cs = min(CE_CHUNK, s)

    @jax.checkpoint
    def ce(args):
        xc, lc = args
        logits = dot("bsd,vd->bsv", xc, head)
        picked = jnp.take_along_axis(logits, lc[..., None], -1)[..., 0]
        return jnp.sum(jax.nn.logsumexp(logits, -1) - picked)

    xs = jnp.moveaxis(x.reshape(bsz, s // cs, cs, d), 1, 0)
    ls = jnp.moveaxis(labels.reshape(bsz, s // cs, cs), 1, 0)
    return jnp.sum(jax.lax.map(ce, (xs, ls)))


ROW_BLOCK = 2             # rows whose gradients are taken together
_LOSS_GRAD_CACHE: Dict = {}


def loss_and_grad(params32, tokens, labels, model, precision="f32"):
    """The mean loss over all positions and its float32 gradient, taken in
    blocks of rows."""
    rows = tokens.shape[0]
    rb = math.gcd(rows, ROW_BLOCK)
    n_pos = tokens.size
    key = (id(model), precision, n_pos)
    fn = _LOSS_GRAD_CACHE.get(key)
    if fn is None:
        def part(p, t, l):
            loss = loss_terms(p, t, l, model, precision) / n_pos
            return loss, loss
        fn = jax.jit(jax.grad(part, has_aux=True))
        _LOSS_GRAD_CACHE[key] = fn
    loss, grads = 0.0, None
    for r in range(0, rows, rb):
        g, lv = fn(params32, tokens[r:r + rb], labels[r:r + rb])
        loss = loss + lv
        grads = g if grads is None else jax.tree_util.tree_map(jnp.add, grads, g)
    return loss, grads


# ---------------------------------------------------------------------------
# DIANA, three steps
# ---------------------------------------------------------------------------

def alpha_inf(block: int) -> float:
    """alpha_inf(B) = 2 / (1 + sqrt(B)) (Lemma 1)."""
    return 2.0 / (1.0 + math.sqrt(block))


def quantize(x, key, block: int):
    """Block ternary p=inf quantization (Def. 2) of one leaf: blocks of
    ``block`` consecutive entries of the flattened leaf, zero-padded."""
    flat = x.reshape(-1)
    m = -(-flat.size // block)
    blocks = jnp.pad(flat, (0, m * block - flat.size)).reshape(m, block)
    scale = jnp.max(jnp.abs(blocks), axis=1, keepdims=True)
    prob = jnp.where(scale > 0, jnp.abs(blocks) / jnp.where(scale > 0, scale, 1.0), 0.0)
    keep = jax.random.uniform(key, blocks.shape, F32) < prob
    q = jnp.where(keep, jnp.sign(blocks) * scale, 0.0)
    return q.reshape(-1)[: flat.size].reshape(x.shape)


class Readings(NamedTuple):
    """What the comparison reads of a run's first three steps."""

    losses: List[float]               # loss of steps 1..3 (mean over workers)
    grad_norms: Dict[str, float]      # per leaf: the first step's direction as the optimizer got it
    change_norms: Dict[str, float]    # per leaf: ||theta_3 - theta_0||
    true_grad_norms: Optional[Dict[str, float]] = None  # per leaf, mean over workers of ||g_i||


@jax.jit
def _leaf_norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(a.astype(F32))))
            for a in jax.tree_util.tree_leaves(tree)]


@jax.jit
def _leaf_change_norms(new, old):
    return [jnp.sqrt(jnp.sum(jnp.square(a.astype(F32) - b.astype(F32))))
            for a, b in zip(jax.tree_util.tree_leaves(new), jax.tree_util.tree_leaves(old))]


def tree_norms(tree) -> Dict[str, float]:
    """Per-leaf float32 norms, keyed by path."""
    vals = jax.device_get(_leaf_norms(tree))
    return {k: float(v) for k, v in zip(leaf_paths(tree), vals)}


def change_norms(new, old) -> Dict[str, float]:
    """Per-leaf norms of ``new - old``, keyed by path."""
    vals = jax.device_get(_leaf_change_norms(new, old))
    return {k: float(v) for k, v in zip(leaf_paths(new), vals)}


def diana_reference(model, traffic, params0, batches, stream: int, *,
                    precision: str = "f32", fault: Optional[str] = None,
                    devices: Sequence = None) -> Readings:
    """Three steps of DIANA with momentum from ``params0``.

    ``batches[t][i]`` is worker i's ``(tokens, labels)`` of step t (numpy).
    ``stream`` seeds the quantizer's draws.  Worker i computes on
    ``devices[i]``; the server and the optimizer live on ``devices[0]``.
    ``fault``: ``"no_exchange"`` serves each worker its own payload alone
    (only worker 0's view is followed), ``"half_batch"`` takes the mean over
    the first half of every worker's tokens.
    """
    n = len(batches[0])
    devices = list(devices or jax.devices())[:n]
    block = int(model["comp_block"])
    alpha = alpha_inf(block) / 2.0
    lr, beta = float(traffic["lr"]), float(traffic["beta"])
    dev0 = devices[0]

    quant = jax.jit(lambda g, h, key: _quantize_tree(g, h, key, block, alpha),
                    donate_argnums=(1,))
    upcast = jax.jit(lambda t: jax.tree_util.tree_map(lambda a: a.astype(F32), t))
    server = jax.jit(lambda acc, hs, mom: _server(acc, hs, mom, n, alpha, beta,
                                                  fault == "no_exchange"),
                     donate_argnums=(1, 2))
    apply = jax.jit(lambda th, mom: jax.tree_util.tree_map(
        lambda p, m_: (p.astype(F32) - lr * m_).astype(p.dtype), th, mom))
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b))

    theta = jax.device_put(params0, dev0)
    h_w = [None] * n
    h_s = mom = None
    losses, grad_norms, true_norms = [], None, None
    base = jax.random.fold_in(jax.random.PRNGKey(0x0D1A), stream % 2**31)
    for t in range(3):
        qs, step_losses, gnorms = [], [], []
        for i, dev in enumerate(devices):        # dispatched without waiting
            tok, lab = batches[t][i]
            if fault == "half_batch":
                tok, lab = _half(tok), _half(lab)
            th_i = upcast(jax.device_put(theta, dev))
            loss, g = loss_and_grad(th_i, jax.device_put(tok, dev),
                                    jax.device_put(lab, dev), model, precision)
            del th_i
            if t == 0:
                gnorms.append(_leaf_norms(g))
            if h_w[i] is None:
                h_w[i] = jax.tree_util.tree_map(jnp.zeros_like, g)
            key = jax.device_put(jax.random.fold_in(jax.random.fold_in(base, t), i), dev)
            q, h_w[i] = quant(g, h_w[i], key)
            del g
            qs.append(q)
            step_losses.append(loss)
        losses.append(sum(float(x) for x in jax.device_get(step_losses)) / n)
        if t == 0:
            paths = leaf_paths(params0)
            per_worker = jax.device_get(gnorms)
            true_norms = {k: sum(float(w[j]) for w in per_worker) / n
                          for j, k in enumerate(paths)}
        acc = qs[0]
        for q in qs[1:]:
            if fault != "no_exchange":
                acc = add(acc, jax.device_put(q, dev0))
        del qs
        if h_s is None:
            h_s = jax.tree_util.tree_map(jnp.zeros_like, acc)
            mom = jax.tree_util.tree_map(jnp.zeros_like, acc)
        ghat, h_s, mom = server(acc, h_s, mom)
        del acc
        if t == 0:
            grad_norms = tree_norms(ghat)
        del ghat
        theta = apply(theta, mom)
    change = change_norms(theta, jax.device_put(params0, dev0))
    return Readings(losses, grad_norms, change, true_norms)


def _half(a):
    a = np.asarray(a)
    if a.shape[0] > 1:
        return a[: a.shape[0] // 2]
    return a[:, : a.shape[1] // 2]


def _quantize_tree(g, h, key, block, alpha):
    leaves_g, treedef = jax.tree_util.tree_flatten(g)
    leaves_h = jax.tree_util.tree_leaves(h)
    qs, hs = [], []
    for j, (gl, hl) in enumerate(zip(leaves_g, leaves_h)):
        q = quantize(gl - hl, jax.random.fold_in(key, j), block)
        qs.append(q)
        hs.append(hl + alpha * q)
    return (jax.tree_util.tree_unflatten(treedef, qs),
            jax.tree_util.tree_unflatten(treedef, hs))


def _server(acc, h_s, mom, n, alpha, beta, own_only):
    dm = acc if own_only else jax.tree_util.tree_map(lambda a: a / n, acc)
    ghat = jax.tree_util.tree_map(jnp.add, h_s, dm)
    h_s = jax.tree_util.tree_map(lambda h, d: h + alpha * d, h_s, dm)
    mom = jax.tree_util.tree_map(lambda m_, g: beta * m_ + g, mom, ghat)
    return ghat, h_s, mom
