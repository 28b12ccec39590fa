"""Plain float32 reference of the benchmark's models and of DIANA training.

Written from the published descriptions and independent of the program
under test: it imports nothing from ``src/`` and takes nothing the program
made.  The weights it starts from come from :func:`make_params`, the
benchmark's own generator, regenerated from the seed.

* The model: embedding, a scan over blocks that each apply the
  configuration's ``pattern`` (``x + mixer(norm1(x))``, then, where ``mlp``
  is not ``"none"``, ``x + mlp(norm2(x))``), final RMSNorm, the tied or
  untied head and a chunked cross-entropy.  Each layer kind of the pattern
  is a module ``bench/layers/<kind>.py`` found by its name (see
  :func:`layer_kind`).
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
import os
from typing import Dict, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import cells

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
VOCAB_PAD = 4096          # the embedding table is padded to a multiple of this
CE_CHUNK = 512            # positions per chunk of the loss
LAYERS = os.path.join(cells.BENCH, "layers")

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
    init: str          # normal | ones | zeros | a name in a layer kind's INITS
    scale: float = 1.0


def padded_vocab(model) -> int:
    return -(-model["vocab"] // VOCAB_PAD) * VOCAB_PAD


def layer_kind(kind: str, layers: str = LAYERS):
    """The module ``<layers>/<kind>.py`` of one layer kind.  It gives
    ``layout(model, stacked)``, the kind's leaves as the program lays them
    out under ``mixer`` or ``mlp``, and ``apply(p, x, model, dot) -> (y,
    aux)``, the layer on its normed input in float32 with every product
    through ``dot``; ``aux`` is a term the loss adds, ``0`` where there is
    none.  Optionally ``INITS``, its own initialisers by name, and
    ``WHOLE_BATCH = True`` where it computes over all of a worker's rows at
    once."""
    return cells.load_module(cells.module_path(layers, kind, "reference for layer kind"))


def pattern_kinds(model, layers: str = LAYERS) -> list:
    """(mixer module, mlp module or None) for each layer of the pattern."""
    return [(layer_kind(spec["mixer"], layers),
             None if spec["mlp"] == "none" else layer_kind(spec["mlp"], layers))
            for spec in model["pattern"]]


def _modules(model, layers: str) -> list:
    """The pattern's layer kinds, each once, in the pattern's order."""
    out = []
    for pair in pattern_kinds(model, layers):
        out += [m for m in pair if m is not None and m not in out]
    return out


def param_layout(model, layers: str = LAYERS) -> dict:
    """The parameter tree as the program lays it out: names, shapes, types
    and the generator's initialisation of each leaf."""
    pdt = model["param_dtype"]
    L = model["n_layers"] // len(model["pattern"])
    d = model["d_model"]
    tree = {"embed": Leaf((padded_vocab(model), d), pdt, "normal", 0.02),
            "final_norm": {"scale": Leaf((d,), pdt, "ones")}}
    if not model["tie_embeddings"]:
        tree["lm_head"] = Leaf((d, padded_vocab(model)), pdt, "normal", 0.02)

    def stacked(shape, dtype, init, scale=1.0):
        return Leaf((L,) + tuple(shape), dtype, init, scale)

    block = {}
    for i, (mixer, mlp) in enumerate(pattern_kinds(model, layers)):
        lp = {"norm1": {"scale": stacked((d,), pdt, "ones")},
              "mixer": mixer.layout(model, stacked)}
        if mlp is not None:
            lp["norm2"] = {"scale": stacked((d,), pdt, "ones")}
            lp["mlp"] = mlp.layout(model, stacked)
        block[f"layer{i}"] = lp
    tree["blocks"] = block
    return tree


def _is_leaf(x) -> bool:
    return isinstance(x, Leaf)


def leaf_paths(tree) -> List[str]:
    """'/'-joined key paths of a dict tree, in ``jax.tree_util`` order."""
    flat = jax.tree_util.tree_flatten_with_path(tree, is_leaf=_is_leaf)[0]
    return ["/".join(str(getattr(k, "key", k)) for k in path) for path, _ in flat]


def _init_leaf(spec: Leaf, key, inits):
    dt = DTYPES[spec.dtype]
    if spec.init == "normal":
        x = jax.random.normal(key, spec.shape, F32) * spec.scale
    elif spec.init == "ones":
        x = jnp.ones(spec.shape, F32)
    elif spec.init == "zeros":
        x = jnp.zeros(spec.shape, F32)
    elif spec.init in inits:
        x = inits[spec.init](key, spec.shape)
    else:
        raise ValueError(spec.init)
    return x.astype(dt)


def _builder(model, layers):
    layout = param_layout(model, layers)
    inits = {}
    for mod in _modules(model, layers):
        for name, fn in getattr(mod, "INITS", {}).items():
            if inits.setdefault(name, fn) is not fn:
                raise ValueError(f"two layer kinds define the initialiser {name!r}")
    leaves, treedef = jax.tree_util.tree_flatten(layout, is_leaf=_is_leaf)

    def build(key):
        keys = jax.random.split(key, len(leaves))
        return jax.tree_util.tree_unflatten(
            treedef, [_init_leaf(s, k, inits) for s, k in zip(leaves, keys)])

    return build


def _weights_key(seed: int):
    return jax.random.fold_in(seed_key(seed), 0x3E16)


def make_params(model, seed: int, layers: str = LAYERS):
    """The benchmark's weights: every leaf from ``seed``, in one jitted call
    on the default device, in the type it is served in.  The program and the
    reference both take them from this one program: on a TPU another
    program that draws the same numbers can round them differently."""
    return jax.jit(_builder(model, layers))(_weights_key(seed))


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


def _add_aux(total, aux):
    """The sum of the layers' terms; a layer without one returns 0 and adds
    nothing, so a pattern without any has no term at all."""
    if isinstance(aux, (int, float)) and aux == 0:
        return total
    return aux if total is None else total + aux


def _block(x, bp, kinds, model, dot):
    aux = None
    for i, (mixer, mlp) in enumerate(kinds):
        lp = bp[f"layer{i}"]
        y, a = mixer.apply(lp["mixer"], rms_norm(x, lp["norm1"]["scale"], model["norm_eps"]),
                           model, dot)
        x, aux = x + y, _add_aux(aux, a)
        if mlp is not None:
            y, a = mlp.apply(lp["mlp"], rms_norm(x, lp["norm2"]["scale"], model["norm_eps"]),
                             model, dot)
            x, aux = x + y, _add_aux(aux, a)
    return x, aux


def loss_terms(params, tokens, labels, model, precision="f32", layers: str = LAYERS):
    """The sum of next-token cross-entropies over every position, and the
    sum of the layers' terms (``None`` where no layer has one).  The
    softmax runs over the padded vocabulary, as the program's head does.
    ``params`` may be of any float type; all math is float32."""
    dot = make_dot(precision)
    kinds = pattern_kinds(model, layers)
    p = jax.tree_util.tree_map(lambda a: a.astype(F32), params)

    def body(x, bp):
        return jax.checkpoint(lambda x_, bp_: _block(x_, bp_, kinds, model, dot))(x, bp)

    x, auxes = jax.lax.scan(body, p["embed"][tokens], p["blocks"])
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
    return jnp.sum(jax.lax.map(ce, (xs, ls))), None if auxes is None else jnp.sum(auxes)


ROW_BLOCK = 2             # rows whose gradients are taken together
_LOSS_GRAD_CACHE: Dict = {}


def loss_and_grad(params32, tokens, labels, model, precision="f32", layers: str = LAYERS):
    """The mean loss over all positions, plus the layers' terms, and its
    float32 gradient, taken in blocks of rows: all of the rows at once where
    a layer kind of the pattern sets ``WHOLE_BATCH``.  Each block adds its
    share of the rows times its terms."""
    rows = tokens.shape[0]
    whole = any(getattr(m, "WHOLE_BATCH", False) for m in _modules(model, layers))
    rb = rows if whole else math.gcd(rows, ROW_BLOCK)
    n_pos = tokens.size
    key = (id(model), precision, n_pos, rows, layers)
    fn = _LOSS_GRAD_CACHE.get(key)
    if fn is None:
        def part(p, t, l):
            ce, aux = loss_terms(p, t, l, model, precision, layers)
            loss = ce / n_pos
            if aux is not None:
                loss = loss + aux * (rb / rows)
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
