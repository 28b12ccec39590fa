"""DIANA (Algorithm 1) — compressed gradient-difference aggregation.

Two implementations, one semantics:

* :func:`aggregate_shardmap` — the production path, called *inside* a
  ``shard_map`` whose manual axes are the DIANA worker axes.  Each worker
  encodes its compressor input, all-gathers the :class:`Payload` wire format
  (the TPU analogue of the paper's MPI Gather + Broadcast — replicated
  deterministic decode replaces the server), and every device reconstructs the
  identical aggregated estimator ``ghat = h^k + mean_i dhat_i``.

* :func:`reference_step` — a single-process n-worker simulation used by unit
  tests, the convex-experiment benchmarks and the paper-figure reproductions.
  ``aggregate_shardmap`` is tested to agree with it bit-for-bit under a shared
  PRNG schedule: both paths run the SAME compressor hooks, and the mean
  accumulates through the same :meth:`Compressor.decode_sum` f32 recurrence.

Every operator-specific decision — what is encoded (gradient vs gradient
difference vs error-corrected gradient), how the memories evolve, how the
gathered payload decodes — lives behind the :class:`Compressor` interface
(:mod:`repro.core.compressors`); this module only owns the pytree plumbing,
the worker collective and the memory-state layout.  For the paper's operator
the hooks are Algorithm 1 lines 5-9:
    h_i^{k+1} = h_i^k + alpha * dhat_i^k
    h^{k+1}   = h^k   + alpha * mean_i dhat_i^k
    ghat^k    = h^k + mean_i dhat_i^k

Every path names its passes with ``jax.named_scope`` (metadata only, read
by the profiler trace): ``diana.round`` around a round, and inside it
``diana.flatten``, ``diana.encode`` (compress_input + compress),
``diana.decode_own``, ``diana.allgather``, ``diana.decode_sum_apply``,
``diana.memory`` and ``diana.unflatten``; ``diana.downlink`` around the
server broadcast.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Sequence

import jax
import jax.numpy as jnp

from .bucket import (
    BucketLayout,
    ChunkedSchedule,
    add_checksum,
    bucketed_compressor,
    fuse_payload,
    payload_recipe,
    unfuse_payload,
    verify_checksum,
    wire_roundtrip,
)
from .compression import CompressionConfig
from .compressors import Compressor, Payload
from .participation import (
    PART_FOLD,
    ParticipationSpec,
    apply_faults,
    direction_scale,
    step_ctx,
)
from .policy import CompressionPolicy, partition_for
from .vr import VRState, control_variate, init_vr, reference_coins, refresh, vr_coin

__all__ = [
    "DianaState",
    "CHUNK_FOLD",
    "DOWN_FOLD",
    "GROUP_FOLD",
    "init_state",
    "init_downlink",
    "downlink_round",
    "aggregate_shardmap",
    "reference_init",
    "reference_step",
    "tree_zeros_like",
    "bucket_layout",
]

# Folded into the UN-worker-folded step key for the downlink draws; disjoint
# from the compression schedule (which folds worker indices then splits over
# leaves) and from the VR coin fold (applied to worker-folded keys), so the
# broadcast's PRNG stream is identical on every worker and never collides
# with an uplink draw.  DESIGN.md §Bidirectional.
DOWN_FOLD = 0x444E  # 'DN'

# Grouped policies: group ``g`` draws from ``fold_in(worker_key, GROUP_FOLD+g)``
# (and the downlink from ``fold_in(down_key, GROUP_FOLD+g)``) — applied AFTER
# the worker fold in both the distributed and reference paths, so the two stay
# bitwise-aligned, and disjoint from VR_FOLD/DOWN_FOLD and from any worker
# index.  UNIFORM policies never fold this: the single-rule path IS the
# pre-policy flat path, draw for draw (DESIGN.md §Policy).
GROUP_FOLD = 0x4750  # 'GP'

# Chunked wire (repro.core.bucket.ChunkedSchedule): chunk ``c`` of a round
# never re-splits keys — it compresses with the SLICE of the monolithic
# per-leaf schedule ``split(key, n_leaves)[bounds[c]:bounds[c+1]]``, which is
# what keeps chunked == monolithic bitwise.  CHUNK_FOLD exists only for the
# compiled-TPU in-kernel-PRNG encodes, which draw one stream per kernel
# launch and cannot honour a per-leaf schedule: chunk ``c`` there draws from
# ``fold_in(key, CHUNK_FOLD + c)`` — distribution-equal, bitwise only within
# a fixed chunking, the same documented exception as that mode's
# bucketed-vs-perleaf story (DESIGN.md §Topology: the PRNG chunk-fold rule).
CHUNK_FOLD = 0x434B  # 'CK'


def _split_spec(spec):
    """Normalize the ``cfg`` argument every entry point takes: returns
    ``(policy, flat_cfg)`` where exactly one is non-None.  A uniform policy
    collapses to its flat config — by construction the identical pre-policy
    code path (the back-compat law); grouped policies return themselves and
    dispatch through the grouped driver."""
    if isinstance(spec, CompressionPolicy):
        if spec.is_uniform:
            return None, spec.flat_config()
        return spec, None
    return None, spec


def tree_zeros_like(tree, dtype=None):
    return jax.tree_util.tree_map(
        lambda x: jnp.zeros(x.shape, dtype or x.dtype), tree
    )


# ---------------------------------------------------------------------------
# Elastic participation plumbing (DESIGN.md §Elasticity)
# ---------------------------------------------------------------------------

def _resolve_participation(policy, cfg):
    """The active :class:`ParticipationSpec`, or None — a trivial spec keeps
    the aggregation on the exact pre-elastic code path, bit for bit."""
    spec = policy.participation if policy is not None else cfg.participation
    if spec is None or spec.is_trivial:
        return None
    return spec


def _where_rows(cond, new, old):
    """Fixed-shape advance/freeze select: ``cond`` is a () or (n,) bool
    broadcast from the left over each leaf's trailing dims.  An explicit
    select — NEVER "add zero" — so frozen state is bitwise-untouched
    (``x + 0.0`` maps ``-0.0`` to ``+0.0``)."""

    def sel(a, b):
        c = cond.reshape(cond.shape + (1,) * (b.ndim - cond.ndim))
        return jnp.where(c, a, b)

    return jax.tree_util.tree_map(sel, new, old)


def _reinit_zero(reinit, h):
    """Zero the ``h`` rows of workers whose churn ``join`` fires this step
    (``reinit`` a () or (n,) bool) — applied BEFORE aggregation, and kept
    even on a degraded step (the freeze selects back to the post-reinit
    state, so a re-joining worker's fresh row survives)."""
    return _where_rows(reinit, tree_zeros_like(h), h)


def _participant_gate(part, valid=None):
    """THE participant-selection rule of one aggregation round: the (n,)
    bool of workers whose ``h_worker``/EF row advances — scheduled
    participants (the PART_FOLD mask), on a non-degraded step, whose wire
    checksum (when faults are armed) verified.  Shared by the per-leaf and
    bucketed reference paths (and mirrored scalar-wise by the distributed
    rounds), so participant selection cannot fork between layouts."""
    gate = part.mask & part.ok
    if valid is not None:
        gate = gate & valid
    return gate


def _masked_server_tail(comp, h_f, total, n_workers, part, m_eff):
    """The sampled-sum server epilogue on ONE flat f32 buffer/leaf:
    the direction uses the RESCALED participant sum (unbiasedness), the
    server memory the UNRESCALED ``sum/n`` (which preserves the invariant
    ``h = mean_i h_i`` — only participants' ``h_i`` advanced), and BOTH
    freeze on a degraded step (``ghat = 0``: skip-update)."""
    scale = direction_scale(part.spec, m_eff, part.ok)
    ghat = jnp.where(part.ok, comp.server_direction(h_f, total * scale),
                     jnp.zeros_like(h_f))
    new_h = jnp.where(part.ok, comp.next_server_memory(h_f, total / n_workers),
                      h_f)
    return ghat, new_h


def _is_payload(t) -> bool:
    return isinstance(t, Payload)


class DianaState(NamedTuple):
    """Compressor state carried by the training loop.

    Memories are stored FLAT — the same layout compression operates in, so
    the entire compress -> gather -> decode -> h-update path is layout-local;
    the only relayouts per step are grads->flat and ghat->param-shape (both
    over the fast intra-pod ICI; see DESIGN.md §Perf notes).  Two layouts:

    * per-leaf (``cfg.bucketed=False``): one 1-D leaf per param leaf —
      h_worker a pytree of ``(n_workers, d_leaf)``, h_server of ``(d_leaf,)``.
    * bucketed (``cfg.bucketed=True``): the whole model in ONE buffer of
      length ``Dp`` (the :class:`~repro.core.bucket.BucketLayout` padded
      size) — h_worker a single ``(n_workers, Dp)`` array, h_server ``(Dp,)``,
      updated by one vectorized elementwise op per step.

    h_worker axis 0 is sharded over the worker mesh axes (each worker holds
    only its own memory): the paper's h_i for alpha-memory operators, the
    error-feedback residual e_i for top-k EF, inert zeros for memoryless
    ones.  h_server is replicated over worker axes — the paper's server-side
    ``h^k = mean_i h_i^k``.

    vr is the optional VR-DIANA slot (:class:`~repro.core.vr.VRState`,
    ``cfg.vr``): per-worker L-SVRG (snapshot, mu) pairs, stored in PARAMETER
    layout (leaves ``(n_workers, *shape)``, worker dim sharded like
    h_worker) regardless of ``cfg.bucketed`` — VR algebra runs before any
    flattening.  ``None`` flattens away, so pre-VR code, checkpoints and
    shardings are untouched when VR is off.

    h_down is the optional DOWNLINK memory (``cfg.down_method``): the
    server-broadcast analogue of h_server — the alpha-memory of an unbiased
    downlink operator, or the error-feedback residual of top-k — REPLICATED
    over the worker axes (server and every worker evolve the identical copy
    deterministically).  Stored flat in the DOWNLINK operator's own layout:
    a pytree of ``(d_leaf,)`` leaves per-leaf, or one ``(Dp_down,)`` buffer
    when the downlink is bucketed.  ``None`` flattens away, so uplink-only
    states, checkpoints and shardings stay byte-identical.
    """

    h_worker: Any
    h_server: Any
    vr: Any = None
    h_down: Any = None


def bucket_layout(cfg: CompressionConfig, tree) -> BucketLayout:
    """The flat-buffer layout of ``tree`` under ``cfg``'s operator (segment
    alignment is the operator's ``bucket_align()``)."""
    return BucketLayout.for_tree(tree, align=cfg.make().bucket_align())


def init_downlink(params, cfg: CompressionConfig, dtype=None, dcfg=None):
    """``h_down^0 = 0`` in the DOWNLINK operator's own layout (``None`` when
    no downlink is configured) — one replicated copy, no worker dim.
    ``dcfg`` overrides the derived ``cfg.down_config()`` (the grouped driver
    passes each rule's standalone downlink config)."""
    dcfg = cfg.down_config() if dcfg is None else dcfg
    if dcfg is None:
        return None
    dtype = cfg.h_dtype if dtype is None else dtype
    if dcfg.bucketed:
        return jnp.zeros((bucket_layout(dcfg, params).padded_size,), dtype)
    return jax.tree_util.tree_map(lambda p: jnp.zeros((p.size,), dtype), params)


def _init_grouped(params, policy: CompressionPolicy, n_workers: int, dtype=None):
    """Per-group memory trees for a grouped policy: dicts keyed by group name
    (``g<rule:02d>_<label>`` — sorted dict order == rule order), each entry in
    that group's own layout: one ``(n, Dp_g)`` / ``(Dp_g,)`` buffer for a
    bucketed group, lists of flat per-leaf memories otherwise.  Returns
    ``(h_worker, h_server, h_down)`` (``h_down`` None when no rule has a
    downlink)."""
    part = partition_for(policy, params)
    groups = part.split(params)
    dtype = policy.h_dtype if dtype is None else dtype
    h_w, h_s, h_d = {}, {}, {}
    for g, gname in enumerate(part.group_names):
        cfg_g, leaves = part.configs[g], groups[g]
        if cfg_g.bucketed:
            dp = bucket_layout(cfg_g, leaves).padded_size
            h_w[gname] = jnp.zeros((n_workers, dp), dtype)
            h_s[gname] = jnp.zeros((dp,), dtype)
        else:
            h_w[gname] = [jnp.zeros((n_workers, l.size), dtype) for l in leaves]
            h_s[gname] = [jnp.zeros((l.size,), dtype) for l in leaves]
        dcfg = part.down_configs[g]
        if dcfg is not None:
            h_d[gname] = init_downlink(leaves, cfg_g, dtype=dtype, dcfg=dcfg)
    return h_w, h_s, (h_d if h_d else None)


def init_state(params, cfg, n_workers: int) -> DianaState:
    """h_i^0 = 0 (the paper's experimental choice) for all operators; the VR
    slot (``cfg.vr``) starts at ``w_i^0 = x^0`` with zero ``mu`` (see
    :func:`repro.core.vr.init_vr` for how callers warm-start ``mu``); the
    downlink memory (``cfg.down_method``) starts at ``h_down^0 = 0``.

    ``cfg`` may be a flat :class:`CompressionConfig` OR a
    :class:`~repro.core.policy.CompressionPolicy`: uniform policies produce
    the byte-identical legacy layout; grouped policies store the memories per
    group (:func:`_init_grouped`)."""
    policy, cfg = _split_spec(cfg)
    if policy is not None:
        vr = init_vr(params, n_workers) if policy.vr else None
        h_w, h_s, h_down = _init_grouped(params, policy, n_workers)
        return DianaState(h_worker=h_w, h_server=h_s, vr=vr, h_down=h_down)
    vr = init_vr(params, n_workers) if cfg.vr else None
    h_down = init_downlink(params, cfg)
    if cfg.bucketed:
        dp = bucket_layout(cfg, params).padded_size
        return DianaState(
            h_worker=jnp.zeros((n_workers, dp), cfg.h_dtype),
            h_server=jnp.zeros((dp,), cfg.h_dtype),
            vr=vr,
            h_down=h_down,
        )
    h_w = jax.tree_util.tree_map(
        lambda p: jnp.zeros((n_workers, p.size), cfg.h_dtype), params
    )
    h_s = jax.tree_util.tree_map(lambda p: jnp.zeros((p.size,), cfg.h_dtype), params)
    return DianaState(h_worker=h_w, h_server=h_s, vr=vr, h_down=h_down)


# ---------------------------------------------------------------------------
# Distributed aggregation (inside shard_map over worker axes)
# ---------------------------------------------------------------------------

@jax.named_scope("diana.allgather")
def _gather_field(a, axis_names, groups=None):
    """All-gather ONE payload field over the worker axes.

    The gathered buffer is explicitly re-constrained to stay sharded over
    'model' on the post-worker dim — ``all_gather`` output sharding does not
    propagate the auto axes by itself and would otherwise replicate the
    payload n times per device.  ``groups`` (hierarchical topology) restricts
    the gather to ``axis_index_groups`` subsets of ONE worker axis — e.g. the
    inter-node leader exchange, whose rows arrive in node order.
    """
    from repro.models.sharding import shard

    if groups is not None:
        assert len(axis_names) == 1, (
            "grouped gathers (hierarchical topology) need ONE worker axis")
        out = jax.lax.all_gather(a, axis_names[0], tiled=False,
                                 axis_index_groups=groups)
    else:
        out = (
            jax.lax.all_gather(a, axis_names, tiled=False)
            if axis_names else a[None]
        )
    return shard(out, None, "model", *(None,) * (out.ndim - 2))


def _gather_payloads(payload_tree, axis_names):
    """All-gather every array field of every per-leaf :class:`Payload`."""

    def gather_leaf(pay: Payload) -> Payload:
        return Payload(*(
            None if f is None else _gather_field(f, axis_names) for f in pay
        ))

    return jax.tree_util.tree_map(gather_leaf, payload_tree, is_leaf=_is_payload)


def _gathered_sum(payload_tree, like, n_workers: int, axis_names,
                  comp: Compressor, mask=None):
    """sum_i decode(payload_i) without materialising n dense copies.

    All-gathers the compressed payload (cheap: n * bits_per_dim * d / 8 bytes)
    and decodes through the compressor's :meth:`decode_sum` — the fused Pallas
    unpack+reduce for kernel-backed operators, a sequential f32 accumulate
    otherwise — so peak memory stays at one dense gradient regardless of n.
    With a participation ``mask``, non-participants' payload rows are zeroed
    first (:meth:`Payload.mask_workers`) so they contribute an exact 0 to the
    unchanged recurrence.
    """
    gathered = _gather_payloads(payload_tree, axis_names)

    like_leaves, treedef = jax.tree_util.tree_flatten(like)
    pay_leaves = jax.tree_util.tree_leaves(gathered, is_leaf=_is_payload)

    outs = []
    with jax.named_scope("diana.decode_sum_apply"):
        for pay, l in zip(pay_leaves, like_leaves):
            if mask is not None:
                pay = pay.mask_workers(mask)
            outs.append(comp.decode_sum(pay, n_workers, l.size))
    return jax.tree_util.tree_unflatten(treedef, outs)


def _gathered_mean(payload_tree, like, n_workers: int, axis_names, comp: Compressor):
    """mean_i decode(payload_i), shaped/typed like ``like``."""
    totals = _gathered_sum(payload_tree, like, n_workers, axis_names, comp)
    with jax.named_scope("diana.decode_sum_apply"):
        return jax.tree_util.tree_map(
            lambda t, l: (t / n_workers).reshape(l.shape).astype(l.dtype),
            totals, like,
        )


def _aggregate_local(grads_local, h_worker, h_server, key, cfg, axis_names,
                     n_workers, part=None):
    """The core Algorithm-1 round on LOCAL arrays (no sharding decisions).

    grads_local leaves may have any shape — they are flattened locally; the
    h leaves are flat ``(1, d_local)`` / ``(d_local,)``.  ``axis_names`` are
    the (manual) worker axes the packed payload is gathered over.  All
    operator behaviour dispatches through the configured compressor's hooks.
    With a participation ctx (``part``), the round is the sampled-sum
    generalisation: every worker still encodes (fixed-shape SPMD), but
    non-participants' gathered payloads decode to exact zeros, the server
    tail rescales, and excluded/frozen state is kept by explicit selects
    (DESIGN.md §Elasticity).
    """
    comp = cfg.make()

    with jax.named_scope("diana.flatten"):
        g_flat = jax.tree_util.tree_map(
            lambda g: g.reshape(-1).astype(jnp.float32), grads_local
        )

    with jax.named_scope("diana.encode"):
        h_local = jax.tree_util.tree_map(
            lambda h: h[0].astype(jnp.float32), h_worker
        )
        if part is not None:
            h_local = _reinit_zero(part.reinit_own, h_local)
        delta = jax.tree_util.tree_map(comp.compress_input, g_flat, h_local)
        if comp.replicate_perleaf:
            # Pin the encode input replicated: sort-selection operators (top-k)
            # RET_CHECK old XLA's partitioner on sharded operands under manual
            # subgroups.  No-op outside GSPMD policies (nested-manual/reference).
            from repro.models.sharding import shard_replicated

            delta = jax.tree_util.tree_map(shard_replicated, delta)

        leaves, treedef = jax.tree_util.tree_flatten(delta)
        keys = jax.random.split(key, len(leaves))
        payloads = [comp.compress(leaf, k) for leaf, k in zip(leaves, keys)]
        payload_tree = jax.tree_util.tree_unflatten(treedef, payloads)
    # The worker's own estimate, for its memory update — decoded from the
    # payload (bitwise the transmitted value); dead-code-eliminated under jit
    # for operators whose hooks ignore it.
    with jax.named_scope("diana.decode_own"):
        dhat_own = jax.tree_util.tree_unflatten(
            treedef, [comp.decode(p, leaf.size) for p, leaf in zip(payloads, leaves)]
        )

    if part is None:
        dhat_mean = _gathered_mean(payload_tree, g_flat, n_workers, axis_names, comp)

        with jax.named_scope("diana.memory"):
            new_h_local = jax.tree_util.tree_map(
                lambda h, dh, dl: comp.next_memory(h, dh, dl).astype(cfg.h_dtype),
                h_local, dhat_own, delta,
            )
            new_hw = jax.tree_util.tree_map(lambda h: h[None], new_h_local)
        with jax.named_scope("diana.decode_sum_apply"):
            new_h_server = jax.tree_util.tree_map(
                lambda h, dm: comp.next_server_memory(h.astype(jnp.float32), dm).astype(cfg.h_dtype),
                h_server, dhat_mean,
            )
            ghat_flat = jax.tree_util.tree_map(
                lambda h, dm: comp.server_direction(h.astype(jnp.float32), dm),
                h_server, dhat_mean,
            )
    else:
        # Sampled sum: per-leaf payloads carry no wire checksum, so the
        # effective set is the scheduled mask itself.
        totals = _gathered_sum(payload_tree, g_flat, n_workers, axis_names,
                               comp, mask=part.mask)
        with jax.named_scope("diana.decode_sum_apply"):
            hs_leaves, hs_def = jax.tree_util.tree_flatten(h_server)
            served = [
                _masked_server_tail(comp, h.astype(jnp.float32), t, n_workers,
                                    part, part.mask)
                for h, t in zip(hs_leaves, jax.tree_util.tree_leaves(totals))
            ]
            ghat_flat = jax.tree_util.tree_unflatten(hs_def, [g for g, _ in served])
            new_h_server = jax.tree_util.tree_unflatten(
                hs_def, [h.astype(cfg.h_dtype) for _, h in served])
        with jax.named_scope("diana.memory"):
            advance = part.m_own & part.ok
            new_h_local = _where_rows(
                advance,
                jax.tree_util.tree_map(comp.next_memory, h_local, dhat_own, delta),
                h_local,
            )
            new_hw = jax.tree_util.tree_map(
                lambda h: h.astype(cfg.h_dtype)[None], new_h_local)

    # Reshape only — ghat stays f32; the caller casts to the gradient dtypes
    # AFTER the (optional) downlink round, so the downlink compresses the
    # same f32 server direction the reference path sees.
    with jax.named_scope("diana.unflatten"):
        ghat = jax.tree_util.tree_map(
            lambda f, g: f.reshape(g.shape), ghat_flat, grads_local
        )
    return ghat, new_hw, new_h_server


@jax.named_scope("diana.allgather")
def _gather_fused(payload: Payload, axis_names, groups=None):
    """All-gather ONE fused uint8 buffer instead of one collective per field.

    Every populated Payload field is byte-cast into a single contiguous
    buffer (:func:`repro.core.bucket.fuse_payload` — exact, bitcast only),
    gathered once over the worker axes, and split back locally — so the whole
    DIANA round really costs one collective, which the trace test in
    ``tests/test_bucket.py`` counts.
    """
    populated = [i for i, f in enumerate(payload) if f is not None]
    if len(populated) == 1:
        # one field IS one collective — skip the byte-cast round-trip, which
        # XLA CPU lowers as slow elementwise loops on full-size payloads
        # (e.g. natural's whole-model int16 codes)
        i = populated[0]
        fields = [None] * len(Payload._fields)
        fields[i] = _gather_field(payload[i], axis_names, groups)
        return Payload(*fields)

    buf = fuse_payload(payload)
    recipe = payload_recipe(payload)
    return unfuse_payload(_gather_field(buf, axis_names, groups), recipe)


# ---------------------------------------------------------------------------
# Two-level (hierarchical) topology + the chunked wire schedule
# ---------------------------------------------------------------------------

def _node_groups(n_workers: int, node_size: int):
    """Intra-node ``axis_index_groups``: consecutive ``node_size`` workers
    form one node (worker w lives on node ``w // node_size``)."""
    return [[b * node_size + r for r in range(node_size)]
            for b in range(n_workers // node_size)]


def _internode_groups(n_workers: int, node_size: int):
    """Inter-node ``axis_index_groups``: one worker of intra-node rank ``r``
    per node, in ascending node order — so every worker's gathered leader
    payloads arrive stacked node 0, 1, ... exactly like the reference
    mirror's node rows.  (Payloads are node-replicated — same delta, same
    node-folded key — so any rank's copy is THE node payload.)"""
    return [[b * node_size + r for b in range(n_workers // node_size)]
            for r in range(node_size)]


def _ordered_node_sum(rows, s: int):
    """The ascending ordered f32 sum over one node's ``s`` worker rows, then
    ``/ s`` — an EXPLICIT recurrence (never psum/pmean, whose reduction order
    the backend owns) shared bit for bit with the reference mirror's
    node pooling."""
    acc = rows[0]
    for i in range(1, s):
        acc = acc + rows[i]
    return acc / s


def _intranode_mean(g_flat, axis_names, n_workers: int, node_size: int):
    """Level 1 of the hierarchical round: the UNcompressed mean of the flat
    gradient buffer over this worker's node (cheap ICI bandwidth), leaving
    every worker holding its node's pooled gradient — the node gradient
    DIANA then compresses once per node instead of once per worker."""
    rows = jax.lax.all_gather(
        g_flat, axis_names[0], tiled=False,
        axis_index_groups=_node_groups(n_workers, node_size))
    return _ordered_node_sum([rows[i] for i in range(node_size)], node_size)


def _node_pool_tree(grads_per_worker, node_size: int):
    """Reference mirror of :func:`_intranode_mean`: pool stacked per-worker
    grads ``(n, ...)`` to per-node means ``(n_nodes, ...)`` with the same
    cast-to-f32 + ascending ordered sum + ``/ s`` recurrence per leaf."""

    def pool(x):
        x = x.astype(jnp.float32)
        xr = x.reshape(-1, node_size, *x.shape[1:])
        return _ordered_node_sum([xr[:, i] for i in range(node_size)],
                                 node_size)

    return jax.tree_util.tree_map(pool, grads_per_worker)


def _hier_node_size(cfg) -> int:
    """The active node size: >1 exactly when the two-level round runs."""
    return cfg.node_size if cfg.topology == "hierarchical" else 1


def _chunk_payloads(cfg, sched: ChunkedSchedule, delta, key):
    """Compress one worker's delta buffer chunk by chunk.

    THE chunk PRNG rule: the monolithic per-leaf schedule is split ONCE and
    sliced per chunk (:meth:`ChunkedSchedule.chunk_keys`), so every leaf
    draws exactly its monolithic bits and sum-of-chunks == monolithic
    bitwise.  ``fold_in(key, CHUNK_FOLD + c)`` feeds only the compiled-TPU
    in-kernel-PRNG encodes (distribution-equal mode — see CHUNK_FOLD).
    """
    base = cfg.make()
    with jax.named_scope("diana.encode"):
        keys = jax.random.split(key, sched.layout.n_leaves)
        return [
            base.compress_bucketed_keys(
                cl, dseg, sched.chunk_keys(keys, c),
                jax.random.fold_in(key, CHUNK_FOLD + c))
            for c, (cl, dseg) in enumerate(
                zip(sched.chunk_layouts, sched.split(delta)))
        ]


@jax.named_scope("diana.decode_own")
def _chunk_decode_own(cfg, sched: ChunkedSchedule, pays):
    """This worker's own dhat over the whole buffer: per-chunk decodes
    concatenated (per-coordinate, so bitwise the monolithic decode)."""
    return jnp.concatenate([
        bucketed_compressor(cfg, cl).decode(pay, cl.padded_size)
        for cl, pay in zip(sched.chunk_layouts, pays)
    ])


def _aggregate_bucketed(grads_local, h_worker, h_server, key, cfg, axis_names,
                        n_workers, part=None, faults=None, step=None):
    """Algorithm-1 round on the WHOLE model as one flat buffer.

    The single-vector formulation of the paper: grads flatten once into the
    static :class:`~repro.core.bucket.BucketLayout`, then the round is ONE
    ``compress`` (one kernel launch for kernel-backed operators), ONE fused
    all-gather, ONE ``decode_sum``, and vectorized elementwise memory
    updates on the flat ``h`` buffers.  Bitwise-equal to
    :func:`_aggregate_local` (the bucketed hooks reproduce the per-leaf PRNG
    schedule and f32 recurrences — see repro.core.bucket).

    With a participation ctx (``part``) the server tail is the sampled-sum
    generalisation (see :func:`_aggregate_local`).  With ``faults`` armed,
    the payload ALWAYS fuses into one uint8 wire buffer, an 8-byte checksum
    is appended (:func:`repro.core.bucket.add_checksum`), the worker's own
    scheduled faults are injected, and the gathered wires verify on every
    receiver — invalid payloads are excluded from the sum exactly like
    non-participants, and the sender's ``h_i`` freezes (the verdict is
    replicated, so the sender knows its payload was discarded).  Under the
    CHUNKED schedule each chunk is its own checksummed wire; a worker is
    excluded whole (valid = AND over its chunk verdicts) so the invariant
    ``h == mean h_i`` never sees a half-applied payload.

    With ``cfg.topology == "hierarchical"`` the round is the Bagua-style
    two-level exchange: the flat gradient buffer first averages UNcompressed
    over this worker's node (:func:`_intranode_mean` — ordered recurrence,
    intra-node ``axis_index_groups``), then the compressed DIANA round runs
    BETWEEN node leaders (``n_eff = n_nodes`` payloads via the inter-node
    groups) with the h-memory kept per node (every worker of a node stores
    the identical node row, so the invariant ``h == mean(h_nodes)`` holds
    exactly).  ``key`` must then be folded with the NODE index, not the
    worker index — aggregate_shardmap documents the caller contract.
    """
    layout = bucket_layout(cfg, grads_local)
    comp = bucketed_compressor(cfg, layout)
    dp = layout.padded_size

    with jax.named_scope("diana.flatten"):
        g_flat = layout.flatten(grads_local)             # (Dp,) f32
    node_size = _hier_node_size(cfg)
    n_eff, groups = n_workers, None
    if node_size > 1:
        assert part is None and faults is None, (
            "hierarchical topology composes with neither participation nor "
            "fault injection — aggregate_shardmap gates this")
        g_flat = _intranode_mean(g_flat, axis_names, n_workers, node_size)
        n_eff = n_workers // node_size
        groups = _internode_groups(n_workers, node_size)

    with jax.named_scope("diana.encode"):
        h_local = h_worker[0].astype(jnp.float32)        # (Dp,)
        if part is not None:
            h_local = jnp.where(part.reinit_own, jnp.zeros_like(h_local), h_local)
        delta = comp.compress_input(g_flat, h_local)

    sched = ChunkedSchedule.for_layout(layout, cfg.chunk_bytes)
    if sched.n_chunks > 1:
        return _aggregate_bucketed_chunked(
            layout, comp, sched, delta, h_local, h_server, key, cfg,
            axis_names, n_eff, groups, n_workers,
            part=part, faults=faults, step=step)

    with jax.named_scope("diana.encode"):
        payload = comp.compress(delta, key)              # ONE Payload
    with jax.named_scope("diana.decode_own"):
        dhat_own = comp.decode(payload, dp)

    if part is None and faults is None:
        gathered = _gather_fused(payload, axis_names, groups)  # ONE collective
        # Fused server tail: decode_sum + mean + direction + memory update in
        # one hook — ONE kernel launch for kernel-backed operators (the
        # epilogue runs on the accumulator tile), the bitwise-identical hook
        # composition otherwise.
        with jax.named_scope("diana.decode_sum_apply"):
            ghat_flat, new_hs_f = comp.decode_sum_apply(
                gathered, n_eff, dp, h_server.astype(jnp.float32)
            )
        with jax.named_scope("diana.memory"):
            new_hw = comp.next_memory(h_local, dhat_own, delta).astype(cfg.h_dtype)[None]
            new_hs = new_hs_f.astype(cfg.h_dtype)
        # f32 leaves — the caller casts to the gradient dtypes after the
        # (optional) downlink round, like the per-leaf path.
        with jax.named_scope("diana.unflatten"):
            ghat = layout.unflatten(ghat_flat, cast=False)
        return ghat, new_hw, new_hs

    valid = None
    if faults is not None:
        buf = fuse_payload(payload)                      # always fuse: the
        # checksum covers the WHOLE wire object, single-field shortcut or not
        wire = apply_faults(add_checksum(buf), faults, step, part.widx)
        flat, valid = verify_checksum(_gather_field(wire, axis_names))
        gathered = unfuse_payload(flat.reshape(-1, *buf.shape),
                                  payload_recipe(payload))
    else:
        gathered = _gather_fused(payload, axis_names)

    m_eff = part.mask if valid is None else part.mask & valid
    with jax.named_scope("diana.decode_sum_apply"):
        total = comp.decode_sum(gathered.mask_workers(m_eff), n_workers, dp)
        ghat_flat, new_hs_f = _masked_server_tail(
            comp, h_server.astype(jnp.float32), total, n_workers, part, m_eff)
    with jax.named_scope("diana.memory"):
        gate = part.m_own & part.ok
        if valid is not None:
            gate = gate & jnp.any(valid & (jnp.arange(n_workers) == part.widx))
        new_h_local = jnp.where(gate, comp.next_memory(h_local, dhat_own, delta),
                                h_local)
    with jax.named_scope("diana.unflatten"):
        ghat = layout.unflatten(ghat_flat, cast=False)
    return (ghat, new_h_local.astype(cfg.h_dtype)[None],
            new_hs_f.astype(cfg.h_dtype))


def _chunk_wire_meta(bufs):
    """Per-chunk fused-wire geometry: each chunk's byte offset into the
    round's concatenated payload body, and the body total — the window
    :func:`repro.core.participation.apply_faults` maps corrupt events
    through."""
    sizes = [int(b.size) for b in bufs]
    offs, acc = [], 0
    for s in sizes:
        offs.append(acc)
        acc += s
    return offs, acc


def _aggregate_bucketed_chunked(layout, comp, sched, delta, h_local, h_server,
                                key, cfg, axis_names, n_eff, groups, n_workers,
                                part=None, faults=None, step=None):
    """The chunked (double-buffered) wire of :func:`_aggregate_bucketed`.

    The fused buffer is split into whole-leaf chunks
    (:class:`~repro.core.bucket.ChunkedSchedule`) and the round is
    software-pipelined: chunk ``c+1``'s all-gather is ISSUED before chunk
    ``c``'s ``decode_sum(+apply)``, so with async collectives the transfer of
    one chunk overlaps the decode of the previous one (the jaxpr-level
    ordering ``tests/test_bucket.py`` proves structurally).  Per-chunk
    results concatenate to bitwise the monolithic round: chunks are
    whole-leaf, keys are slices of the monolithic schedule, and every
    decode/apply recurrence is per-coordinate.  Worker-side memory updates
    stay monolithic — only the wire is chunked.
    """
    cls_ = sched.chunk_layouts
    comps = [bucketed_compressor(cfg, cl) for cl in cls_]
    pays = _chunk_payloads(cfg, sched, delta, key)
    dhat_own = _chunk_decode_own(cfg, sched, pays)
    h_s = h_server.astype(jnp.float32)
    hs_chunks = sched.split(h_s)
    C = sched.n_chunks

    if part is None and faults is None:
        # Double-buffered pipeline: gather c+1 in flight while c decodes.
        gathered = [None] * C
        gathered[0] = _gather_fused(pays[0], axis_names, groups)
        ghat_parts, hs_parts = [], []
        for c in range(C):
            if c + 1 < C:
                gathered[c + 1] = _gather_fused(pays[c + 1], axis_names, groups)
            with jax.named_scope("diana.decode_sum_apply"):
                g_c, h_c = comps[c].decode_sum_apply(
                    gathered[c], n_eff, cls_[c].padded_size, hs_chunks[c])
            ghat_parts.append(g_c)
            hs_parts.append(h_c)
        with jax.named_scope("diana.decode_sum_apply"):
            ghat_flat = jnp.concatenate(ghat_parts)
            new_hs_f = jnp.concatenate(hs_parts)
        with jax.named_scope("diana.memory"):
            new_hw = comp.next_memory(h_local, dhat_own, delta).astype(cfg.h_dtype)[None]
        with jax.named_scope("diana.unflatten"):
            ghat = layout.unflatten(ghat_flat, cast=False)
        return ghat, new_hw, new_hs_f.astype(cfg.h_dtype)

    valid = None
    if faults is not None:
        # Per-chunk wires, each with its own checksum tail; corrupt events
        # address the concatenated body (so they land in exactly one chunk),
        # drop/delay break every tail.  All gathers are issued before any
        # verify/decode — the collectives still overlap the decode work.
        bufs = [fuse_payload(p) for p in pays]
        offs, body_total = _chunk_wire_meta(bufs)
        wires = [
            apply_faults(add_checksum(bufs[c]), faults, step, part.widx,
                         byte_offset=offs[c], body_total=body_total)
            for c in range(C)
        ]
        gw = [_gather_field(w, axis_names) for w in wires]
        gathereds, valids = [], []
        for c in range(C):
            flat, v_c = verify_checksum(gw[c])
            valids.append(v_c)
            gathereds.append(unfuse_payload(flat.reshape(-1, *bufs[c].shape),
                                            payload_recipe(pays[c])))
        # Whole-worker exclusion: ANY corrupted chunk discards the worker's
        # round (a half-applied payload would break h == mean h_i).
        valid = valids[0]
        for v_c in valids[1:]:
            valid = valid & v_c
    else:
        gathereds = [None] * C
        gathereds[0] = _gather_fused(pays[0], axis_names, groups)
        for c in range(1, C):
            gathereds[c] = _gather_fused(pays[c], axis_names, groups)

    m_eff = part.mask if valid is None else part.mask & valid
    with jax.named_scope("diana.decode_sum_apply"):
        total = jnp.concatenate([
            comps[c].decode_sum(gathereds[c].mask_workers(m_eff), n_workers,
                                cls_[c].padded_size)
            for c in range(C)
        ])
        ghat_flat, new_hs_f = _masked_server_tail(
            comp, h_s, total, n_workers, part, m_eff)
    with jax.named_scope("diana.memory"):
        gate = part.m_own & part.ok
        if valid is not None:
            gate = gate & jnp.any(valid & (jnp.arange(n_workers) == part.widx))
        new_h_local = jnp.where(gate, comp.next_memory(h_local, dhat_own, delta),
                                h_local)
    with jax.named_scope("diana.unflatten"):
        ghat = layout.unflatten(ghat_flat, cast=False)
    return (ghat, new_h_local.astype(cfg.h_dtype)[None],
            new_hs_f.astype(cfg.h_dtype))


# ---------------------------------------------------------------------------
# Downlink: the compressed server broadcast (DESIGN.md §Bidirectional)
# ---------------------------------------------------------------------------

@jax.named_scope("diana.downlink")
def downlink_round(ghat, h_down, down_key: jax.Array, cfg: CompressionConfig,
                   *, h_dtype=None, dcfg=None):
    """Pass the aggregated direction ``ghat`` through the DOWNLINK compressor.

    The gradient-difference trick DIANA applies uplink, applied to the server
    broadcast: the (replicated, deterministic) server encodes
    ``delta = compress_input(ghat, h_down)`` — ``ghat - h_down`` for
    alpha-memory operators, the error-compensated ``ghat + e`` for top-k EF —
    puts the payload on the broadcast (fused into ONE uint8 wire object in
    the bucketed layout — :func:`wire_roundtrip`, bitcast-exact; per-leaf
    payloads stay unfused, mirroring the uplink), and every receiver
    reconstructs
    ``server_direction(h_down, decode(payload))`` and advances the shared
    memory with ``next_memory``.  Because ``ghat``, ``h_down`` and
    ``down_key`` are identical on all workers, the broadcast needs no
    collective here — replicated determinism plays the server, exactly as the
    uplink's replicated decode does (DESIGN.md §3).

    Runs AFTER ``server_direction`` on the param-shaped ``ghat`` tree and
    makes its own layout decision (``cfg.down_config().bucketed``), so it
    composes with every uplink operator, both uplink layouts, and VR.
    ``down_key`` must be the step key folded with :data:`DOWN_FOLD` BEFORE
    any worker fold — the broadcast draws are worker-independent.

    Returns ``(ghat_hat, new_h_down)`` with ``ghat_hat`` shaped and typed
    like ``ghat``.  ``dcfg`` overrides the derived ``cfg.down_config()`` —
    grouped policies pass each rule's standalone downlink config (which may
    carry its own block size / norm power, inexpressible on a flat config).
    """
    dcfg = cfg.down_config() if dcfg is None else dcfg
    assert dcfg is not None, "downlink_round needs cfg.down_method"
    h_dtype = cfg.h_dtype if h_dtype is None else h_dtype

    if dcfg.bucketed:
        layout = bucket_layout(dcfg, ghat)
        comp = bucketed_compressor(dcfg, layout)
        g = layout.flatten(ghat)
        h = h_down.astype(jnp.float32)
        delta = comp.compress_input(g, h)
        sched = ChunkedSchedule.for_layout(layout, dcfg.chunk_bytes)
        if sched.n_chunks > 1:
            # Chunked broadcast wire: each chunk rides its own fused uint8
            # wire object (the same schedule as the uplink), decodes
            # per-coordinate and concatenates — bitwise the monolithic
            # broadcast.
            pays = [wire_roundtrip(p)
                    for p in _chunk_payloads(dcfg, sched, delta, down_key)]
            dhat = _chunk_decode_own(dcfg, sched, pays)
        else:
            pay = wire_roundtrip(comp.compress(delta, down_key))
            dhat = comp.decode(pay, layout.padded_size)
        ghat_hat = layout.unflatten(comp.server_direction(h, dhat), cast=True)
        new_h = comp.next_memory(h, dhat, delta).astype(h_dtype)
        return ghat_hat, new_h

    comp = dcfg.make()
    g_flat = jax.tree_util.tree_map(
        lambda x: x.reshape(-1).astype(jnp.float32), ghat
    )
    h = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), h_down)
    delta = jax.tree_util.tree_map(comp.compress_input, g_flat, h)
    if comp.replicate_perleaf:
        # Same partitioner pin as the uplink per-leaf encode (see
        # _aggregate_local) — the broadcast encode runs in the same
        # partial-manual body.
        from repro.models.sharding import shard_replicated

        delta = jax.tree_util.tree_map(shard_replicated, delta)
    leaves, treedef = jax.tree_util.tree_flatten(delta)
    keys = jax.random.split(down_key, len(leaves))
    # Per-leaf payloads stay UNfused, mirroring the uplink (only the bucketed
    # layout builds the single wire buffer): the fuse bitcasts RET_CHECK old
    # XLA's partitioner under partial-manual bodies with live auto inner
    # axes — exactly the meshes resolve_bucketed downgrades to this layout.
    pays = [comp.compress(leaf, k) for leaf, k in zip(leaves, keys)]
    dhat = jax.tree_util.tree_unflatten(
        treedef, [comp.decode(p, leaf.size) for p, leaf in zip(pays, leaves)]
    )
    ghat_hat = jax.tree_util.tree_map(
        lambda hh, dh, g: comp.server_direction(hh, dh).reshape(g.shape).astype(g.dtype),
        h, dhat, ghat,
    )
    new_h = jax.tree_util.tree_map(
        lambda hh, dh, dl: comp.next_memory(hh, dh, dl).astype(h_dtype),
        h, dhat, delta,
    )
    return ghat_hat, new_h


@jax.named_scope("diana.round")
def aggregate_shardmap(
    grads_local,
    state: DianaState,
    key: jax.Array,
    cfg: CompressionConfig,
    *,
    axis_names: Sequence[str],
    n_workers: int,
    inner_axes: Sequence[str] = (),
    grad_specs=None,
    h_specs=None,
    mesh=None,
    vr_aux=None,
    params_local=None,
    vr_force_refresh=None,
    down_key=None,
    part_key=None,
    step=None,
    worker_index=None,
    faults=None,
    telemetry: bool = False,
):
    """One DIANA aggregation round inside a shard_map body.

    grads_local — this worker's local gradient pytree (g_i^k).
    state.h_worker leaves arrive with local leading dim 1 (own memory only).
    key          — already folded with the worker index (deterministic stream).

    With ``state.vr`` present (``cfg.vr``) the round is VR-DIANA
    (repro.core.vr): the compressor consumes the control-variated estimator
    ``k_i = g_i - grad f_{ij}(w_i) + mu_i`` instead of ``g_i``, and the
    (snapshot, mu) pair refreshes with the worker's Bernoulli(``cfg.vr_p``)
    coin drawn from ``fold_in(key, VR_FOLD)``.  Callers must then supply

    * ``vr_aux = (grads_at_snapshot, mu_candidate)`` — this worker's
      gradient at its snapshot ``w_i`` on the SAME minibatch, and the value
      ``mu_i`` takes on refresh (the full local gradient at ``x^k`` in the
      finite-sum setting; the minibatch gradient in the streaming trainer);
      both parameter-shaped local trees (no leading worker dim);
    * ``params_local`` — the current iterate ``x^k`` (the refreshed snapshot);
    * optionally ``vr_force_refresh`` — a traced bool OR-ed into the coin
      (the trainer forces a refresh at step 0 to populate a zeros-init mu).

    The VR algebra runs on parameter-shaped trees BEFORE any layout
    decision, so it composes with every operator in both the per-leaf and
    bucketed layouts, and ``ghat`` is cast back to the gradients' dtypes.

    With ``state.h_down`` present (``cfg.down_method``) the round is
    BIDIRECTIONAL: the aggregated direction is itself passed through the
    downlink compressor (:func:`downlink_round`) before being returned, and
    callers must supply ``down_key = fold_in(key, DOWN_FOLD)`` computed from
    the step key BEFORE the worker fold (the broadcast draws are identical on
    every worker — repro.launch.train does this).

    With ``cfg.bucketed`` the round runs on the whole-model flat buffer
    (:func:`_aggregate_bucketed`: one compress, one fused all-gather, one
    decode_sum) and ``state`` must carry the bucketed single-buffer layout
    from :func:`init_state`; callers on toolchains where that cannot lower
    (live auto inner axes on old XLA) must downgrade the config first —
    ``repro.launch.train.resolve_bucketed`` owns that decision.

    When ``inner_axes`` (the non-worker mesh axes, e.g. ('model',) or
    ('data','model')) are given together with per-leaf PartitionSpecs, the
    whole round runs inside a NESTED fully-manual shard_map: each inner
    device encodes / decodes ITS OWN shard of every gradient leaf and the
    payload all-gather runs over the (outer-manual) worker axes.  No
    relayout, no partitioner decisions — XLA's SPMD partitioner crashes on
    several of them under manual subgroups (DESIGN.md §6).  The h memory is
    stored in this shard-local flat layout, which is self-consistent step to
    step (its global ordering is internal state, never interpreted).

    With a non-trivial ``participation`` spec on the config/policy the round
    is ELASTIC (DESIGN.md §Elasticity): callers must supply

    * ``part_key = fold_in(step_key, PART_FOLD)`` — derived BEFORE the
      worker fold, like ``down_key`` (the (n,) mask is identical on every
      worker);
    * ``worker_index`` — this worker's linear index (a traced scalar is
      fine: own-bit extraction is an elementwise one-hot reduce);
    * ``step`` — the scalar step counter, required when the spec has a churn
      schedule (and always with ``faults``).

    ``faults`` (a :class:`~repro.core.participation.FaultPlan`, may be
    empty) arms the wire checksum; it requires the flat BUCKETED layout
    (the checksum rides the fused uint8 wire buffer).

    Returns ``(ghat, new_state)`` with ``ghat`` identical on all workers and
    shaped/sharded like ``grads_local``.  With ``telemetry=True`` the return
    is ``(ghat, new_state, telem)`` where ``telem`` is a fixed-shape
    :class:`~repro.core.telemetry.GroupTelemetry` measured on the served
    (f32, post-downlink) direction — a pure observer computed from already
    replicated values (zero extra collectives); ``ghat``/``new_state`` are
    bitwise identical to the ``telemetry=False`` call.
    """
    axis_names = tuple(axis_names)
    inner_axes = tuple(inner_axes)
    policy, cfg = _split_spec(cfg)
    vr_p = policy.vr_p if policy is not None else cfg.vr_p

    spec = _resolve_participation(policy, cfg)
    if spec is None and faults is not None:
        spec = ParticipationSpec()  # checksum-only mode: all-true mask,
        # exclusion algebra driven purely by checksum verdicts
    part = None
    if spec is not None:
        assert part_key is not None, (
            "elastic aggregation needs part_key = fold_in(step_key, "
            "PART_FOLD) derived BEFORE the worker fold (identical on all "
            "workers)")
        assert worker_index is not None, (
            "elastic aggregation needs worker_index (this worker's linear "
            "index on the worker mesh axes)")
        if spec.churn or faults is not None:
            assert step is not None, (
                "a churn schedule / fault plan needs the scalar step counter")
        part = step_ctx(spec, part_key, n_workers,
                        0 if step is None else step, worker_index)
    if faults is not None:
        assert policy is None and cfg.bucketed, (
            "fault injection rides the bucketed fused wire buffer — use a "
            "flat cfg with bucketed=True")
    if policy is not None and policy.topology == "hierarchical":
        raise NotImplementedError(
            "hierarchical topology currently runs only on flat (uniform) "
            "bucketed configs — grouped policies keep topology='flat'")
    if cfg is not None and _hier_node_size(cfg) > 1:
        # Two-level rounds compose with neither elasticity nor VR (the node
        # mean is an uncompressed barrier over healthy in-node workers), and
        # the group partition is a single worker axis by construction.
        # Callers must fold ``key`` with the NODE index (widx // node_size),
        # not the worker index — the inter-node exchange is one DIANA round
        # over node leaders and the reference scans over nodes.
        assert spec is None and faults is None and state.vr is None, (
            "topology='hierarchical' composes with neither participation/"
            "faults nor VR")
        assert len(axis_names) == 1, (
            "topology='hierarchical' needs a single worker mesh axis (the "
            "node groups are index windows on one axis)")
        assert n_workers % cfg.node_size == 0, (
            f"node_size={cfg.node_size} must divide n_workers={n_workers}")

    grads_in = grads_local
    new_vr = state.vr
    if state.vr is not None:
        assert vr_p is not None, (
            "VR aggregation needs a concrete snapshot probability — resolve "
            "cfg.vr_p (repro.core.vr.resolve_vr_p) before building the step")
        assert vr_aux is not None and params_local is not None, (
            "VR aggregation needs vr_aux=(grads_at_snapshot, mu_candidate) "
            "and params_local")
        g_snap, mu_cand = vr_aux
        mu_own = jax.tree_util.tree_map(
            lambda m: m[0].astype(jnp.float32), state.vr.mu
        )
        grads_in = control_variate(grads_local, g_snap, mu_own)
        coins = vr_coin(key, vr_p)[None]
        if vr_force_refresh is not None:
            coins = coins | jnp.asarray(vr_force_refresh, bool)
        if part is not None:
            # Frozen-memory rule: a non-participant's (snapshot, mu) must not
            # advance, and nothing advances on a degraded step.  Gated on the
            # SCHEDULED mask only — never the checksum verdict: a corrupted
            # wire is receiver-side, the local snapshot refresh already
            # happened (repro.core.vr).
            coins = coins & (part.m_own & part.ok)
        new_vr = refresh(
            state.vr, coins, params_local,
            jax.tree_util.tree_map(lambda g: g[None], mu_cand),
        )

    if policy is not None:
        ghat, new_hw, new_hs, new_h_down = _aggregate_grouped(
            grads_in, state, key, policy,
            axis_names=axis_names, n_workers=n_workers, inner_axes=inner_axes,
            grad_specs=grad_specs, h_specs=h_specs, mesh=mesh,
            down_key=down_key, part=part,
        )
    else:
        ghat, new_hw, new_hs = _dispatch_round(
            grads_in, state, key, cfg,
            axis_names=axis_names, n_workers=n_workers, inner_axes=inner_axes,
            grad_specs=grad_specs, h_specs=h_specs, mesh=mesh,
            part=part, faults=faults, step=step,
        )
        new_h_down = state.h_down
        if state.h_down is not None:
            assert down_key is not None, (
                "bidirectional aggregation needs down_key = fold_in(step_key, "
                "DOWN_FOLD) derived BEFORE the worker fold (identical on all "
                "workers)")
            ghat, new_h_down = downlink_round(ghat, state.h_down, down_key, cfg)
            if part is not None:
                # Degraded step: nothing to broadcast — the downlink memory
                # freezes and ghat stays zero.  (On non-degraded steps every
                # worker — participant or not — advances the replicated
                # h_down: the broadcast is modelled as received by all.)
                new_h_down = _where_rows(part.ok, new_h_down, state.h_down)
                ghat = jax.tree_util.tree_map(
                    lambda g: jnp.where(part.ok, g, jnp.zeros_like(g)), ghat)
    telem = None
    if telemetry:
        # Measured on the f32 served direction BEFORE the dtype restore —
        # the exact bits the reference path telemeters, so distributed and
        # reference telemetry agree bitwise like ghat does.
        from .telemetry import measure as _measure_telemetry

        telem = _measure_telemetry(
            policy, ghat, ok=None if part is None else part.ok)
    # The round (and the downlink, when on) ran in f32 — the bits the
    # reference path produces; restore the caller's gradient dtypes here so
    # the optimizer state layout is independent of the vr/downlink flags.
    ghat = jax.tree_util.tree_map(
        lambda f, g: f.astype(g.dtype), ghat, grads_local
    )
    new_state = DianaState(h_worker=new_hw, h_server=new_hs, vr=new_vr,
                           h_down=new_h_down)
    if telemetry:
        return ghat, new_state, telem
    return ghat, new_state


def _pspec_leaf(s) -> bool:
    from jax.sharding import PartitionSpec as P

    return isinstance(s, P)


def _aggregate_grouped(
    grads_local, state, key, policy: CompressionPolicy, *,
    axis_names, n_workers, inner_axes, grad_specs, h_specs, mesh, down_key,
    part=None,
):
    """One aggregation round of a GROUPED policy inside the shard_map body.

    The partition (cached, pure function of (policy, tree structure)) splits
    the gradient tree into per-rule groups; each group then runs the SAME
    sub-round the flat path runs — the pmean fast path for identity groups,
    :func:`_aggregate_bucketed` on the group's own
    :class:`~repro.core.bucket.BucketLayout` (one compress, one fused
    all-gather, one decode_sum PER GROUP), or the per-leaf round — with the
    group-folded key ``fold_in(worker_key, GROUP_FOLD+g)``, so mixed operators
    share one aggregation step.  Groups with a ``down`` spec pass their slice
    of the server direction through their own downlink compressor before the
    merge.  Returns ``(ghat, h_worker, h_server, h_down)`` with the state
    trees as group-name dicts (matching :func:`_init_grouped`).

    Participation is POLICY-level: the one ctx (``part``, resolved by the
    caller from the pre-group-fold PART_FOLD stream) applies to every group
    — a worker is in or out of the whole step, never of one group — so the
    mask draw count is independent of the group structure.
    """
    part_ = partition_for(policy, grads_local)
    g_groups = part_.split(grads_local)
    spec_groups = (part_.split(grad_specs, is_leaf=_pspec_leaf)
                   if grad_specs is not None else None)
    hspec_groups = (part_.split(h_specs, is_leaf=_pspec_leaf)
                    if h_specs is not None else None)

    ghat_groups = []
    new_hw, new_hs, new_hd = {}, {}, {}
    for g, gname in enumerate(part_.group_names):
        cfg_g = part_.configs[g]
        comp = cfg_g.make()
        gkey = jax.random.fold_in(key, GROUP_FOLD + g)
        hw_g, hs_g = state.h_worker[gname], state.h_server[gname]
        if comp.prefers_allreduce and part is None:
            # identity's pmean fast path only without participation: the
            # masked round must gather + mask + decode_sum (the reference
            # recurrence), which also brings identity under the bitwise
            # contract whenever a mask is live
            ghat_g = [
                jax.lax.pmean(gr, axis_names) if axis_names else gr
                for gr in g_groups[g]
            ]
        elif cfg_g.bucketed:
            ghat_g, hw_g, hs_g = _aggregate_bucketed(
                g_groups[g], hw_g, hs_g, gkey, cfg_g, axis_names, n_workers,
                part=part)
        else:
            ghat_g, hw_g, hs_g = _perleaf_round(
                g_groups[g], hw_g, hs_g, gkey, cfg_g,
                axis_names=axis_names, n_workers=n_workers,
                inner_axes=inner_axes,
                grad_specs=spec_groups[g] if spec_groups is not None else None,
                h_specs=hspec_groups[g] if hspec_groups is not None else None,
                mesh=mesh, part=part)
        dcfg = part_.down_configs[g]
        if dcfg is not None:
            assert down_key is not None, (
                "a policy with downlink rules needs down_key = "
                "fold_in(step_key, DOWN_FOLD) derived BEFORE the worker fold")
            ghat_g, hd_g = downlink_round(
                ghat_g, state.h_down[gname],
                jax.random.fold_in(down_key, GROUP_FOLD + g), cfg_g,
                dcfg=dcfg, h_dtype=policy.h_dtype)
            if part is not None:
                hd_g = _where_rows(part.ok, hd_g, state.h_down[gname])
                ghat_g = jax.tree_util.tree_map(
                    lambda x: jnp.where(part.ok, x, jnp.zeros_like(x)), ghat_g)
            new_hd[gname] = hd_g
        ghat_groups.append(ghat_g)
        new_hw[gname] = hw_g
        new_hs[gname] = hs_g
    ghat = part_.merge(ghat_groups)
    return ghat, new_hw, new_hs, (new_hd if new_hd else None)


def _dispatch_round(
    grads_local, state, key, cfg, *,
    axis_names, n_workers, inner_axes, grad_specs, h_specs, mesh,
    part=None, faults=None, step=None,
):
    """Route one (possibly control-variated) gradient tree through the
    layout-appropriate Algorithm-1 round; returns ``(ghat, new_hw, new_hs)``."""
    comp = cfg.make()
    if comp.prefers_allreduce and part is None:
        # dense stateless payload: the gathered mean IS a fused all-reduce.
        # Under participation the masked gather+decode_sum path runs instead
        # — identity then joins the bitwise reference contract.
        ghat = jax.tree_util.tree_map(
            lambda g: jax.lax.pmean(g, axis_names) if axis_names else g,
            grads_local,
        )
        return ghat, state.h_worker, state.h_server

    if cfg.bucketed:
        # The flat buffer is ONE global object, so the bucketed round always
        # runs with the inner (non-worker) axes auto: GSPMD relayouts the
        # leaf shards into/out of the buffer over fast intra-pod ICI, and the
        # nested fully-manual mode (whose point is per-leaf shard-local
        # encode/decode) does not apply — a shard-local sub-layout is future
        # work, tracked in DESIGN.md §Perf.
        return _aggregate_bucketed(
            grads_local, state.h_worker, state.h_server, key, cfg,
            axis_names, n_workers, part=part, faults=faults, step=step,
        )

    return _perleaf_round(
        grads_local, state.h_worker, state.h_server, key, cfg,
        axis_names=axis_names, n_workers=n_workers, inner_axes=inner_axes,
        grad_specs=grad_specs, h_specs=h_specs, mesh=mesh, part=part,
    )


def _perleaf_round(grads_local, h_worker, h_server, key, cfg, *,
                   axis_names, n_workers, inner_axes, grad_specs, h_specs,
                   mesh, part=None):
    """The per-leaf Algorithm-1 round, nested-manual where the toolchain and
    caller-provided specs allow (DESIGN.md §6), local otherwise.  Shared by
    the flat path and by each per-leaf GROUP of a grouped policy (whose trees
    are leaf lists — any pytree works)."""
    if not inner_axes or grad_specs is None or part is not None:
        # single-device / tests: everything already local.  Participation
        # also takes this branch: the ctx's traced mask arrays cannot ride
        # the nested-manual body's closure, and under GSPMD auto inner axes
        # the local round is correct (the nested-manual mode is a perf
        # specialisation, not a semantics change).
        return _aggregate_local(
            grads_local, h_worker, h_server, key, cfg, axis_names, n_workers,
            part=part,
        )

    from jax.sharding import PartitionSpec as P

    from repro.compat import shard_map as _shard_map
    from repro.models.sharding import NoopPolicy, sharding_policy

    amesh = None
    try:
        amesh = jax.sharding.get_abstract_mesh()
    except Exception:
        pass
    if amesh is None or amesh.empty:
        amesh = mesh  # plain-jit caller (no outer shard_map): concrete mesh
    assert amesh is not None, "aggregate_shardmap needs a mesh for the nested map"

    def body(grads, h_w, h_s, k):
        with sharding_policy(NoopPolicy()):
            return _aggregate_local(grads, h_w, h_s, k, cfg, axis_names, n_workers)

    hw_specs = jax.tree_util.tree_map(lambda s: P(None, *s), h_specs,
                                      is_leaf=_pspec_leaf)
    in_specs = (grad_specs, hw_specs, h_specs, P())
    out_specs = (grad_specs, hw_specs, h_specs)
    return _shard_map(
        body, mesh=amesh, in_specs=in_specs, out_specs=out_specs,
        axis_names=set(inner_axes), check_vma=False,
    )(grads_local, h_worker, h_server, key)


# ---------------------------------------------------------------------------
# Single-process n-worker reference (tests, convex experiments, figures)
# ---------------------------------------------------------------------------

class ReferenceState(NamedTuple):
    h_worker: Any  # (n, d) per leaf — flat, mirroring DianaState (or ONE
                   # (n, Dp) buffer in bucketed mode)
    h_server: Any  # (d,) per leaf — flat (or (Dp,) bucketed)
    v: Any         # momentum buffer, like params
    vr: Any = None # optional VR-DIANA slot, mirroring DianaState.vr
    h_down: Any = None  # optional downlink memory, mirroring DianaState.h_down


def reference_init(params, cfg, n_workers: int) -> ReferenceState:
    policy, cfg = _split_spec(cfg)
    if policy is not None:
        vr = init_vr(params, n_workers) if policy.vr else None
        h_w, h_s, h_down = _init_grouped(params, policy, n_workers,
                                         dtype=jnp.float32)
        return ReferenceState(h_worker=h_w, h_server=h_s,
                              v=tree_zeros_like(params, jnp.float32),
                              vr=vr, h_down=h_down)
    vr = init_vr(params, n_workers) if cfg.vr else None
    h_down = init_downlink(params, cfg, dtype=jnp.float32)
    if cfg.bucketed:
        dp = bucket_layout(cfg, params).padded_size
        return ReferenceState(
            h_worker=jnp.zeros((n_workers, dp), jnp.float32),
            h_server=jnp.zeros((dp,), jnp.float32),
            v=tree_zeros_like(params, jnp.float32),
            vr=vr,
            h_down=h_down,
        )
    return ReferenceState(
        h_worker=jax.tree_util.tree_map(
            lambda p: jnp.zeros((n_workers, p.size), jnp.float32), params
        ),
        h_server=jax.tree_util.tree_map(
            lambda p: jnp.zeros((p.size,), jnp.float32), params
        ),
        v=tree_zeros_like(params, jnp.float32),
        vr=vr,
        h_down=h_down,
    )


@jax.named_scope("diana.round")
def reference_step(
    grads_per_worker,
    state: ReferenceState,
    key: jax.Array,
    cfg: CompressionConfig,
    *,
    beta: float = 0.0,
    vr_aux=None,
    params=None,
    vr_force_refresh=None,
    step=None,
    faults=None,
    telemetry: bool = False,
):
    """Aggregate stacked per-worker grads (n, ...) exactly as Algorithm 1.

    Bit-for-bit aligned with :func:`aggregate_shardmap`: worker ``i`` draws
    from ``fold_in(key, i)`` through the same compress path (per-leaf or
    bucketed, by ``cfg.bucketed``), and the mean runs through the same
    :meth:`Compressor.decode_sum` sequential f32 recurrence as the
    distributed decode — tests assert exact equality between the two, and
    between the two layouts.

    With ``state.vr`` present (``cfg.vr``) this is VR-DIANA: the stacked
    gradients are control-variated against the per-worker (snapshot, mu)
    state before compression, and the snapshots refresh on per-worker
    Bernoulli(``cfg.vr_p``) coins — the SAME draws and where-selects as the
    distributed path (repro.core.vr's PRNG schedule contract), so bitwise
    equality extends to VR runs.  ``vr_aux = (grads_at_snapshot,
    mu_candidate)`` stacks the distributed per-worker aux trees
    (``(n, *shape)`` leaves) and ``params`` is the current iterate.

    With ``state.h_down`` present (``cfg.down_method``) the aggregated
    direction additionally passes through the downlink compressor
    (:func:`downlink_round`) before the momentum accumulate — the same
    code and the same ``fold_in(key, DOWN_FOLD)`` stream as the distributed
    path, so bitwise equality extends to bidirectional runs.

    The bucketed path scans over workers (``lax.scan``: one traced body
    regardless of n).  The per-leaf cross-check path deliberately keeps the
    unrolled Python loop: its callers (the convex experiments and the paper
    figures) drive it EAGERLY step by step, where an un-jitted scan would
    re-trace its body on every call — the unrolled ops dispatch faster, and
    under jit both forms compile to the same per-worker program.

    With a non-trivial ``participation`` spec the round is ELASTIC: the
    (n,) mask draws from ``fold_in(key, PART_FOLD)`` — the identical stream
    the distributed path receives as ``part_key`` — and ``step`` (default 0)
    drives the churn schedule.  ``faults`` arms the wire checksum exactly as
    in :func:`aggregate_shardmap` (flat bucketed configs only).

    Returns (v, new_state): ``v = beta*v + ghat`` — caller does the prox step.
    With ``telemetry=True``: ``(v, new_state, telem)``, the telemetry being
    the same pure-observer :class:`~repro.core.telemetry.GroupTelemetry` the
    distributed path emits (measured on the f32 served direction, before the
    momentum accumulate) — bitwise equal across the two paths.
    """
    policy, cfg = _split_spec(cfg)
    vr_p = policy.vr_p if policy is not None else cfg.vr_p

    spec = _resolve_participation(policy, cfg)
    if spec is None and faults is not None:
        spec = ParticipationSpec()
    part = None
    if spec is not None:
        if spec.churn or faults is not None:
            assert step is not None, (
                "a churn schedule / fault plan needs the step= kwarg")
        nw = jax.tree_util.tree_leaves(grads_per_worker)[0].shape[0]
        part = step_ctx(spec, jax.random.fold_in(key, PART_FOLD), nw,
                        0 if step is None else step)
    if faults is not None:
        assert policy is None and cfg.bucketed, (
            "fault injection rides the bucketed fused wire buffer — use a "
            "flat cfg with bucketed=True")
    if policy is not None and policy.topology == "hierarchical":
        raise NotImplementedError(
            "hierarchical topology currently runs only on flat (uniform) "
            "bucketed configs — grouped policies keep topology='flat'")
    if cfg is not None and _hier_node_size(cfg) > 1:
        # Mirror of the aggregate_shardmap gate: two-level rounds compose
        # with neither elasticity nor VR, and worker count must tile into
        # whole nodes.  The scan inside _reference_agg_bucketed then runs
        # over nodes with fold_in(key, node) — the node key the distributed
        # callers fold.
        assert spec is None and faults is None and state.vr is None, (
            "topology='hierarchical' composes with neither participation/"
            "faults nor VR")
        nw = jax.tree_util.tree_leaves(grads_per_worker)[0].shape[0]
        assert nw % cfg.node_size == 0, (
            f"node_size={cfg.node_size} must divide n_workers={nw}")

    new_vr = state.vr
    if state.vr is not None:
        assert vr_p is not None, (
            "VR reference step needs a concrete cfg.vr_p "
            "(repro.core.vr.resolve_vr_p)")
        assert vr_aux is not None and params is not None, (
            "VR reference step needs vr_aux=(grads_at_snapshot, mu_candidate) "
            "and params")
        g_snap, mu_cand = vr_aux
        grads_per_worker = control_variate(grads_per_worker, g_snap, state.vr.mu)
        nw = jax.tree_util.tree_leaves(grads_per_worker)[0].shape[0]
        coins = reference_coins(key, vr_p, nw)
        if vr_force_refresh is not None:
            coins = coins | jnp.asarray(vr_force_refresh, bool)
        if part is not None:
            # Snapshots refresh only for participants on a non-degraded step
            # — the scheduled mask, never the wire-checksum verdict (the
            # distributed coins are drawn before the gather).
            coins = coins & _participant_gate(part)
        new_vr = refresh(state.vr, coins, params, mu_cand)

    if policy is not None:
        ghat, new_hw, new_hs, new_hd = _reference_grouped(
            grads_per_worker, state, key, policy, part=part)
        v = jax.tree_util.tree_map(lambda v0, g: beta * v0 + g, state.v, ghat)
        out_state = state._replace(h_worker=new_hw, h_server=new_hs, v=v,
                                   vr=new_vr, h_down=new_hd)
        if telemetry:
            from .telemetry import measure as _measure_telemetry

            telem = _measure_telemetry(
                policy, ghat, ok=None if part is None else part.ok)
            return v, out_state, telem
        return v, out_state

    if cfg.bucketed:
        ghat, new_hw, new_hs = _reference_agg_bucketed(
            grads_per_worker, state.h_worker, state.h_server, key, cfg,
            part=part, faults=faults, step=step)
    else:
        ghat, new_hw, new_hs = _reference_agg_perleaf(
            grads_per_worker, state.h_worker, state.h_server, key, cfg,
            part=part)
    new_state = state._replace(h_worker=new_hw, h_server=new_hs)
    v, out_state, served = _reference_finish(
        ghat, state, new_state, new_vr, key, cfg, beta, part=part)
    if telemetry:
        from .telemetry import measure as _measure_telemetry

        telem = _measure_telemetry(
            None, served, ok=None if part is None else part.ok)
        return v, out_state, telem
    return v, out_state


def _reference_grouped(grads_per_worker, state, key, policy: CompressionPolicy,
                       part=None):
    """The reference-path mirror of :func:`_aggregate_grouped`: the same
    partition, the same per-group sub-rounds, the same
    ``fold_in(worker_key, GROUP_FOLD+g)`` draws (the group fold is applied
    AFTER the worker fold on both paths) and the same per-group downlink
    streams ``fold_in(fold_in(key, DOWN_FOLD), GROUP_FOLD+g)`` — so grouped
    distributed and reference runs stay bitwise-aligned for every
    non-identity operator (identity keeps its documented pmean exemption —
    which, like the distributed side, is suspended whenever a participation
    ctx is live, because a masked round must run the gather+decode_sum
    recurrence).  The ONE policy-level ``part`` ctx applies to every group."""
    part_ = partition_for(policy, grads_per_worker)
    g_groups = part_.split(grads_per_worker)
    ghat_groups = []
    new_hw, new_hs, new_hd = {}, {}, {}
    for g, gname in enumerate(part_.group_names):
        cfg_g = part_.configs[g]
        hw_g, hs_g = state.h_worker[gname], state.h_server[gname]
        agg = (_reference_agg_bucketed if cfg_g.bucketed
               else _reference_agg_perleaf)
        ghat_g, hw_g, hs_g = agg(g_groups[g], hw_g, hs_g, key, cfg_g,
                                 gfold=GROUP_FOLD + g, part=part)
        dcfg = part_.down_configs[g]
        if dcfg is not None:
            ghat_g, hd_g = downlink_round(
                ghat_g, state.h_down[gname],
                jax.random.fold_in(jax.random.fold_in(key, DOWN_FOLD),
                                   GROUP_FOLD + g),
                cfg_g, dcfg=dcfg, h_dtype=jnp.float32)
            if part is not None:
                hd_g = _where_rows(part.ok, hd_g, state.h_down[gname])
                ghat_g = jax.tree_util.tree_map(
                    lambda x: jnp.where(part.ok, x, jnp.zeros_like(x)), ghat_g)
            new_hd[gname] = hd_g
        ghat_groups.append(ghat_g)
        new_hw[gname] = hw_g
        new_hs[gname] = hs_g
    return part_.merge(ghat_groups), new_hw, new_hs, (new_hd if new_hd else None)


def _worker_key(key, w, gfold):
    """The per-worker compression key: ``fold_in(key, w)``, then the group
    fold for grouped policies — matching the distributed side, where the
    worker fold happens at the caller and the group fold in
    :func:`_aggregate_grouped`."""
    k = jax.random.fold_in(key, w)
    if gfold is not None:
        k = jax.random.fold_in(k, gfold)
    return k


def _reference_agg_perleaf(grads_per_worker, h_worker, h_server, key, cfg,
                           gfold=None, part=None):
    """The per-leaf reference AGGREGATION on any pytree of stacked per-worker
    grads (full trees on the flat path, leaf lists per policy group);
    returns ``(ghat, new_h_worker, new_h_server)``.  With a participation
    ctx the round is the sampled-sum generalisation of
    :func:`_aggregate_local`: churn-join rows re-init first, every worker
    still encodes, non-participants' stacked payload rows decode to exact
    zeros (:meth:`Payload.mask_workers`), the server tail runs
    :func:`_masked_server_tail` and only :func:`_participant_gate` rows
    advance their memory."""
    comp = cfg.make()
    n = jax.tree_util.tree_leaves(grads_per_worker)[0].shape[0]
    if part is not None:
        h_worker = _reinit_zero(part.reinit, h_worker)

    payload_trees = []
    new_h_rows = []
    for w in range(n):
        gw = jax.tree_util.tree_map(
            lambda g: g[w].astype(jnp.float32).reshape(-1), grads_per_worker
        )
        hw = jax.tree_util.tree_map(
            lambda h: h[w].astype(jnp.float32), h_worker
        )
        with jax.named_scope("diana.encode"):
            delta = jax.tree_util.tree_map(comp.compress_input, gw, hw)
            leaves, treedef = jax.tree_util.tree_flatten(delta)
            keys = jax.random.split(_worker_key(key, w, gfold), len(leaves))
            payloads = [comp.compress(leaf, k) for leaf, k in zip(leaves, keys)]
        with jax.named_scope("diana.decode_own"):
            dhat_w = jax.tree_util.tree_unflatten(
                treedef, [comp.decode(p, leaf.size) for p, leaf in zip(payloads, leaves)]
            )
        payload_trees.append(jax.tree_util.tree_unflatten(treedef, payloads))
        with jax.named_scope("diana.memory"):
            new_h_rows.append(jax.tree_util.tree_map(
                comp.next_memory, hw, dhat_w, delta
            ))

    # Stack per-worker payloads into the gathered layout (leading worker axis)
    # and decode through the same summation path as the distributed server.
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *payload_trees)
    like_leaves, treedef = jax.tree_util.tree_flatten(
        jax.tree_util.tree_map(
            lambda g: g[0].astype(jnp.float32).reshape(-1), grads_per_worker
        )
    )
    pay_leaves = jax.tree_util.tree_leaves(stacked, is_leaf=_is_payload)
    hs_leaves = jax.tree_util.tree_leaves(h_server)
    if part is None:
        with jax.named_scope("diana.decode_sum_apply"):
            served = [
                comp.decode_sum_apply(pay, n, l.size, hs)
                for pay, l, hs in zip(pay_leaves, like_leaves, hs_leaves)
            ]
        new_hw = jax.tree_util.tree_map(
            lambda *rows: jnp.stack(rows), *new_h_rows)
    else:
        # Sampled sum — the same decode_sum + _masked_server_tail composition
        # as the distributed masked round (per-leaf payloads carry no wire
        # checksum, so the effective set is the scheduled mask).
        served = [
            _masked_server_tail(
                comp, hs.astype(jnp.float32),
                comp.decode_sum(pay.mask_workers(part.mask), n, l.size),
                n, part, part.mask)
            for pay, l, hs in zip(pay_leaves, like_leaves, hs_leaves)
        ]
        new_hw = _where_rows(
            _participant_gate(part),
            jax.tree_util.tree_map(lambda *rows: jnp.stack(rows), *new_h_rows),
            h_worker,
        )
    ghat_flat = jax.tree_util.tree_unflatten(treedef, [g for g, _ in served])
    new_hs = jax.tree_util.tree_unflatten(treedef, [h for _, h in served])
    ghat = jax.tree_util.tree_map(
        lambda f, g: f.reshape(g.shape[1:]), ghat_flat, grads_per_worker
    )
    return ghat, new_hw, new_hs


def _reference_finish(ghat, state, new_state, new_vr, key, cfg, beta,
                      part=None):
    """Shared reference tail: the downlink round (when configured) on the
    param-shaped ``ghat`` — the SAME :func:`downlink_round` the distributed
    path runs, with the same ``fold_in(key, DOWN_FOLD)`` stream — then the
    momentum accumulate ``v = beta*v + ghat``.  On a degraded elastic step
    the downlink memory freezes and ``ghat`` re-zeros (the broadcast carries
    nothing), mirroring the distributed flat tail.  Also returns the served
    (post-downlink, pre-momentum) ``ghat`` so the caller can telemeter the
    same f32 direction the distributed path measures."""
    new_h_down = state.h_down
    if state.h_down is not None:
        ghat, new_h_down = downlink_round(
            ghat, state.h_down, jax.random.fold_in(key, DOWN_FOLD), cfg,
            h_dtype=jnp.float32,
        )
        if part is not None:
            new_h_down = _where_rows(part.ok, new_h_down, state.h_down)
            ghat = jax.tree_util.tree_map(
                lambda g: jnp.where(part.ok, g, jnp.zeros_like(g)), ghat)
    v = jax.tree_util.tree_map(lambda v0, g: beta * v0 + g, state.v, ghat)
    return v, new_state._replace(v=v, vr=new_vr, h_down=new_h_down), ghat


def _reference_agg_bucketed(grads_per_worker, h_worker, h_server, key, cfg,
                            gfold=None, part=None, faults=None, step=None):
    """The bucketed reference AGGREGATION (uplink only — downlink and
    momentum live in the callers' shared tails): scan over workers, each
    round ONE compress on the flattened model (or policy group); ONE fused
    decode_sum+apply over the scan-stacked payload.  The worker loop stays a
    ``lax.scan`` on purpose: an eagerly-unrolled loop compiles each
    ``compress`` in its own context, and XLA is free to reassociate the p=2
    block-norm reduction differently there — 1-ulp scale drift against the
    per-leaf reference (same compile-context sensitivity as the FMA
    contraction note in kernels/sparse.py).  Bitwise-equal to the per-leaf
    reference (same draws, same recurrences) and to the distributed bucketed
    path.

    Hierarchical topology mirrors the two-level distributed round: grads
    pool to node means first (:func:`_node_pool_tree` — the identical
    ordered recurrence the shardmap path uses), the scan then runs over
    NODES with the node-leader h rows, and the returned worker memory
    re-duplicates each node row over its workers so ``h == mean(h_i)``
    holds over workers and nodes alike.  The chunked schedule mirrors the
    chunked wire: the scan stacks a tuple of per-chunk payloads (same
    monolithic key slices, see CHUNK_FOLD note), each decode_sum(+apply)
    runs per chunk against the matching ``h_server`` slice, and the
    results concatenate — bitwise the monolithic round."""
    node_size = _hier_node_size(cfg)
    if node_size > 1:
        assert part is None and faults is None, (
            "hierarchical topology composes with neither participation nor "
            "fault injection (reference_step gates this)")
        grads_per_worker = _node_pool_tree(grads_per_worker, node_size)
        # Rows within a node are identical by construction (see the
        # re-duplication below), so the leader rows ARE the node memories.
        h_worker = h_worker[::node_size]
    layout = bucket_layout(cfg, jax.tree_util.tree_map(
        lambda g: g[0], grads_per_worker
    ))
    comp = bucketed_compressor(cfg, layout)
    dp = layout.padded_size
    n = jax.tree_util.tree_leaves(grads_per_worker)[0].shape[0]
    if part is not None:
        h_worker = _reinit_zero(part.reinit, h_worker)

    sched = ChunkedSchedule.for_layout(layout, cfg.chunk_bytes)
    chunked = sched.n_chunks > 1
    cls_ = sched.chunk_layouts
    comps = [bucketed_compressor(cfg, cl) for cl in cls_] if chunked else []
    base = cfg.make()

    def worker_round(_, xs):
        w, g_row, h_row = xs
        with jax.named_scope("diana.flatten"):
            flat_g = layout.flatten(g_row)
        with jax.named_scope("diana.encode"):
            delta = comp.compress_input(flat_g, h_row)
            wkey = _worker_key(key, w, gfold)
            if chunked:
                keys = jax.random.split(wkey, layout.n_leaves)
                payload = tuple(
                    base.compress_bucketed_keys(
                        cl, dseg, sched.chunk_keys(keys, c),
                        jax.random.fold_in(wkey, CHUNK_FOLD + c))
                    for c, (cl, dseg) in enumerate(zip(cls_, sched.split(delta))))
            else:
                payload = comp.compress(delta, wkey)
        with jax.named_scope("diana.decode_own"):
            if chunked:
                dhat_w = jnp.concatenate([
                    comps[c].decode(payload[c], cls_[c].padded_size)
                    for c in range(sched.n_chunks)])
            else:
                dhat_w = comp.decode(payload, dp)
        with jax.named_scope("diana.memory"):
            return None, (payload, comp.next_memory(h_row, dhat_w, delta))

    _, (stacked, new_h) = jax.lax.scan(
        worker_round, None,
        (jnp.arange(n), grads_per_worker, h_worker),
    )
    if part is None and faults is None:
        with jax.named_scope("diana.decode_sum_apply"):
            if chunked:
                hs_chunks = sched.split(h_server)
                served = [
                    comps[c].decode_sum_apply(stacked[c], n,
                                              cls_[c].padded_size, hs_chunks[c])
                    for c in range(sched.n_chunks)
                ]
                ghat_flat = jnp.concatenate([g for g, _ in served])
                new_hs = jnp.concatenate([h for _, h in served])
            else:
                ghat_flat, new_hs = comp.decode_sum_apply(stacked, n, dp, h_server)
        # f32, like the per-leaf ref
        with jax.named_scope("diana.unflatten"):
            ghat = layout.unflatten(ghat_flat, cast=False)
        if node_size > 1:
            # Every worker of a node stores the identical node memory row.
            new_h = jnp.repeat(new_h, node_size, axis=0)
        return ghat, new_h, new_hs

    chunks = list(stacked) if chunked else [stacked]
    valid = None
    if faults is not None:
        # The wire mirror of the distributed fault path: fuse each worker's
        # own payload PER CHUNK wire, checksum each, inject that worker's
        # scheduled faults through the chunk's byte window, then verify each
        # stack exactly as the receivers do post-gather.  A worker is
        # excluded whole when ANY of its chunk wires fails.
        bufs = [fuse_payload(ch.select(0)) for ch in chunks]
        offs, body_total = _chunk_wire_meta(bufs)
        gathered_chunks, valids = [], []
        for c, ch in enumerate(chunks):
            wires = [
                apply_faults(add_checksum(fuse_payload(ch.select(w))),
                             faults, step, w,
                             byte_offset=offs[c],
                             body_total=body_total if chunked else None)
                for w in range(n)
            ]
            flat, v_c = verify_checksum(jnp.stack(wires))
            valids.append(v_c)
            gathered_chunks.append(unfuse_payload(
                flat.reshape(n, *bufs[c].shape), payload_recipe(ch.select(0))))
        valid = valids[0]
        for v_c in valids[1:]:
            valid = valid & v_c
    else:
        gathered_chunks = chunks

    m_eff = part.mask if valid is None else part.mask & valid
    if chunked:
        total = jnp.concatenate([
            comps[c].decode_sum(gathered_chunks[c].mask_workers(m_eff), n,
                                cls_[c].padded_size)
            for c in range(sched.n_chunks)])
    else:
        total = comp.decode_sum(gathered_chunks[0].mask_workers(m_eff), n, dp)
    ghat_flat, new_hs_f = _masked_server_tail(
        comp, h_server.astype(jnp.float32), total, n, part, m_eff)
    new_h = _where_rows(_participant_gate(part, valid), new_h, h_worker)
    return layout.unflatten(ghat_flat, cast=False), new_h, new_hs_f
