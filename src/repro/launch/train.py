"""Distributed DIANA training step + CLI training driver.

Topology-aware composition (DESIGN.md §3):

    jit( shard_map(local_step, manual=worker_axes) )

* manual axes = the DIANA worker axes.  Inside the body ``jax.grad`` yields
  each worker's LOCAL gradient (no implicit cross-worker reduce) — exactly the
  ``g_i^k`` Algorithm 1 needs.
* everything else ('model', and 'data' in hierarchical mode) stays auto:
  GSPMD lowers the tensor/expert parallelism from the logical-axis
  annotations in the model code, and ZeRO/FSDP-shards params + optimizer
  state over the inner data axes when the workers are pods.
* the compressed all-gather + replicated decode inside
  ``core.diana.aggregate_shardmap`` is the paper's Gather+Broadcast.

Paper-faithful mode: ``worker_axes=('pod','data')`` — every data slice is a
worker, params replicated over data.  Hierarchical (beyond-paper):
``worker_axes=('pod',)`` — compress only the slow inter-pod link.
"""

from __future__ import annotations

import argparse
import functools
import warnings
import math
import time
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.profiler import StepTraceAnnotation, TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.compat import shard_map

from repro.configs import get_config, get_shape, input_specs
from repro.core.compression import CompressionConfig
from repro.core.diana import DianaState, aggregate_shardmap, bucket_layout
from repro.core.policy import CompressionPolicy, load_policy, partition_for
from repro.core.vr import VRState, resolve_vr_p
from repro.models import init_model, train_loss
from repro.models.sharding import GSPMDPolicy, sharding_policy
from repro.optim import DianaOptimizer, momentum, adamw, constant_schedule
from repro.optim.diana_optimizer import DianaOptState

from .mesh import (
    data_axes,
    make_mesh,
    make_production_mesh,
    resolve_train_mesh,
    worker_axes_in,
    worker_count,
)
from .sharding_rules import batch_specs, param_specs

__all__ = ["build_train_step", "train_state_shardings", "init_train_state", "make_optimizer",
           "resolve_bucketed", "resolved_layout", "resolve_policy_arg", "TrainRun"]


def resolve_bucketed(opt: "DianaOptimizer", mesh, waxes) -> "DianaOptimizer":
    """Downgrade bucketed -> per-leaf aggregation when it cannot lower.

    The flat-buffer round concatenates every (model-sharded) leaf into ONE
    buffer, which requires resharding under the manual worker subgroup; old
    XLA's SPMD partitioner RET_CHECKs on those patterns whenever an auto
    inner axis (size > 1) is live inside the partial-manual body (DESIGN.md
    §6).  On such toolchains (no nested-manual support) the step silently
    falls back to the per-leaf layout — bitwise the same results, just more
    collectives.  Pure worker meshes (the paper's data-parallel setting) and
    nested-manual-capable toolchains keep the bucketed path.  The DOWNLINK
    flatten (core.diana.downlink_round) builds the same kind of whole-model
    buffer inside the same partial-manual body, so the downgrade forces its
    layout per-leaf too.  For a grouped policy the downgrade applies to
    EVERY group, both directions (``CompressionPolicy.force_perleaf``).

    Resolved HERE (not inside core.diana) because the choice fixes the
    DianaState layout: init and step must agree before the state is built.
    """
    pol = opt.policy
    if not pol.any_bucketed():
        return opt
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    inner_live = any(sizes[a] > 1 for a in mesh.axis_names if a not in waxes)
    from repro.compat import supports_nested_manual

    if inner_live and not supports_nested_manual():
        live = tuple(a for a in mesh.axis_names
                     if a not in waxes and sizes[a] > 1)
        warnings.warn(
            "resolve_bucketed: downgrading the aggregation layout "
            f"[reason=no-nested-manual inner_axes={live} "
            "resulting_layout=per-leaf topology=flat]: the flat-buffer round "
            "cannot lower with live auto inner axes on this toolchain "
            "(DESIGN.md §6).  Results are bitwise identical; step time and "
            "collective count are not.",
            RuntimeWarning, stacklevel=2)
        return opt.replace(policy=pol.force_perleaf())
    return opt


def resolved_layout(opt: "DianaOptimizer", mesh, waxes) -> str:
    """The layout :func:`resolve_bucketed` actually runs on this mesh —
    ``"bucketed"``, ``"per-leaf"``, or ``"per-leaf (downgraded)"`` when the
    config asked for bucketed but the toolchain forced the fallback.  Bench
    rows surface this so a silent-looking downgrade is visible in results."""
    if not opt.policy.any_bucketed():
        return "per-leaf"
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        resolved = resolve_bucketed(opt, mesh, waxes)
    return ("bucketed" if resolved.policy.any_bucketed()
            else "per-leaf (downgraded)")


def resolve_policy_arg(cfg, policy) -> CompressionPolicy:
    """The trainer's ``--comp-policy`` surface -> a concrete policy.

    ``policy`` is a :class:`CompressionPolicy`, a ``.json`` file path, an
    inline rule string (``repro.core.policy.parse_rules`` syntax), the
    literal ``"default"`` selecting the model's curated default
    (``ModelConfig.comp_policy``), or the literal ``"size-adaptive"``
    building :meth:`CompressionPolicy.size_adaptive` from the model's own
    parameter tree (small leaves dense, the bulk quantized by the model's
    flat ``compression`` method).  The model config supplies the model-wide
    fields (worker axes, layout default, h dtype, VR) unless a JSON document
    overrides them.
    """
    if policy == "default":
        if cfg.comp_policy is None:
            raise ValueError(
                f"--comp-policy default: {cfg.name} defines no default "
                f"policy (ModelConfig.comp_policy is None)")
        policy = cfg.comp_policy
    if policy == "size-adaptive":
        from repro.core.policy import ChannelSpec

        params_shape = jax.eval_shape(
            lambda k: init_model(cfg, k), jax.ShapeDtypeStruct((2,), "uint32"))
        return CompressionPolicy.size_adaptive(
            params_shape,
            large=ChannelSpec(method=cfg.compression, k=cfg.comp_k,
                              block_size=cfg.comp_block, p=cfg.comp_p),
            bucketed=cfg.comp_bucketed,
            worker_axes=cfg.comp_worker_axes,
            h_dtype=cfg.h_dtype,
            vr=cfg.vr,
            vr_p=cfg.vr_p,
        )
    return load_policy(
        policy,
        bucketed=cfg.comp_bucketed,
        worker_axes=cfg.comp_worker_axes,
        h_dtype=cfg.h_dtype,
        vr=cfg.vr,
        vr_p=cfg.vr_p,
    )


def make_optimizer(cfg, *, lr: float = 3e-4, inner: str = "momentum", beta: float = 0.9,
                   compression: Optional[CompressionConfig] = None,
                   policy=None, participation=None) -> DianaOptimizer:
    """Build the training optimizer from a model config.

    ``policy`` (a :class:`CompressionPolicy` | inline rule string | ``.json``
    path | ``"default"``) selects per-parameter-group compression; without it
    the flat ``cfg.compression``/``comp_*`` fields build the legacy uniform
    config (bitwise the pre-policy behaviour).  ``participation`` (a
    :class:`~repro.core.participation.ParticipationSpec`) attaches elastic
    client sampling / dropout / churn to either surface — it is model-wide,
    so it rides the policy whole (DESIGN.md §Elasticity).
    """
    inner_opt = adamw() if inner == "adamw" else momentum(beta)
    if policy is not None:
        if compression is not None:
            raise ValueError("pass either compression= or policy=, not both")
        return DianaOptimizer(inner=inner_opt, schedule=constant_schedule(lr),
                              policy=resolve_policy_arg(cfg, policy),
                              participation=participation)
    comp = compression or CompressionConfig(
        method=cfg.compression,
        p=cfg.comp_p,
        block_size=cfg.comp_block,
        k=cfg.comp_k,
        worker_axes=cfg.comp_worker_axes,
        h_dtype=cfg.h_dtype,
        bucketed=cfg.comp_bucketed,
        vr=cfg.vr,
        vr_p=cfg.vr_p,
        down_method=cfg.comp_down_method,
        down_k=cfg.comp_down_k,
    )
    return DianaOptimizer(comp, inner_opt, schedule=constant_schedule(lr),
                          participation=participation)


# ---------------------------------------------------------------------------
# Sharding of the training state
# ---------------------------------------------------------------------------

def train_state_shardings(cfg, opt: DianaOptimizer, mesh, params_shape, opt_state_shape):
    """NamedSharding pytrees for (params, opt_state) — on the RESOLVED train
    mesh (see mesh.resolve_train_mesh); callers must place batches there too."""
    mesh, waxes = resolve_train_mesh(mesh, opt.policy.worker_axes)
    opt = resolve_bucketed(opt, mesh, waxes)
    fsdp = tuple(a for a in data_axes(mesh) if a not in waxes)
    pspecs = param_specs(params_shape, cfg, mesh, fsdp_axes=fsdp)
    p_shard = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), pspecs)

    wtuple = waxes if len(waxes) != 1 else waxes[0]

    vr_shard = None
    if opt.policy.vr:
        # VR (snapshot, mu) mirror the params' inner sharding with the worker
        # dim prepended (manual-sharded like h_worker) — fsdp axes and waxes
        # are disjoint by construction, so the specs never collide.
        def to_vr(s):
            return NamedSharding(mesh, P(wtuple if waxes else None, *s))

        vr_leaf = lambda s: isinstance(s, P)
        vr_shard = VRState(
            snapshot=jax.tree_util.tree_map(to_vr, pspecs, is_leaf=vr_leaf),
            mu=jax.tree_util.tree_map(to_vr, pspecs, is_leaf=vr_leaf),
        )

    msize = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)

    if not opt.policy.is_uniform:
        diana_shard = _grouped_diana_shardings(
            opt.policy, mesh, params_shape, pspecs, msize=msize,
            wtuple=wtuple, waxes=waxes, vr_shard=vr_shard)
        inner_shard = _inner_shardings(opt_state_shape.inner, p_shard, mesh)
        return p_shard, DianaOptState(
            step=NamedSharding(mesh, P()), inner=inner_shard, diana=diana_shard)

    # Downlink memory: replicated over the worker axes (server + every worker
    # evolve the same copy); the flat dim shards like the h_server analogue —
    # over 'model' when the bucketed downlink buffer divides evenly, per the
    # leaf's h spec in the per-leaf downlink layout.
    down_shard = None
    dcfg = opt.compression.down_config()
    if dcfg is not None:
        if dcfg.bucketed:
            dpd = bucket_layout(dcfg, params_shape).padded_size
            down_axis = "model" if msize > 1 and dpd % msize == 0 else None
            down_shard = NamedSharding(mesh, P(down_axis))
        else:
            down_shard = jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), h_flat_specs(pspecs)
            )

    if opt.compression.bucketed:
        # Single flat (n, Dp) / (Dp,) memory buffers: worker dim manual-
        # sharded; the flat dim shards over 'model' when the padded size
        # divides evenly (block-aligned layouts usually do), else replicates.
        # The replicate fallback only matters on nested-manual-capable
        # toolchains (resolve_bucketed downgrades live-model meshes on old
        # XLA) — for big align-1 operators there, pad the layout rather than
        # accept n_workers x Dp replicas; NOT done here because mesh-dependent
        # padding would fork the state layout across meshes and break the
        # bitwise per-leaf contract.
        dp = bucket_layout(opt.compression, params_shape).padded_size
        flat_axis = "model" if msize > 1 and dp % msize == 0 else None
        diana_shard = DianaState(
            h_worker=NamedSharding(mesh, P(wtuple if waxes else None, flat_axis)),
            h_server=NamedSharding(mesh, P(flat_axis)),
            vr=vr_shard,
            h_down=down_shard,
        )
    else:
        h_specs = h_flat_specs(pspecs)
        diana_shard = DianaState(
            h_worker=jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, P(wtuple if waxes else None, *s)), h_specs
            ),
            h_server=jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), h_specs),
            vr=vr_shard,
            h_down=down_shard,
        )
    # inner optimizer state mirrors params (momentum/adam buffers)
    inner_shard = _inner_shardings(opt_state_shape.inner, p_shard, mesh)
    opt_shard = DianaOptState(
        step=NamedSharding(mesh, P()), inner=inner_shard, diana=diana_shard
    )
    return p_shard, opt_shard


def _grouped_diana_shardings(pol, mesh, params_shape, pspecs, *, msize,
                             wtuple, waxes, vr_shard):
    """NamedSharding dicts for a grouped policy's per-group memory trees:
    each group gets the same treatment its layout would get model-wide —
    single flat (n, Dp_g)/(Dp_g,) buffers sharded over 'model' when the
    group's padded size divides evenly (bucketed), per-leaf h specs derived
    from the group's param specs otherwise; downlink memories replicated over
    the worker axes like the uniform case."""
    part = partition_for(pol, params_shape)
    p_groups = part.split(params_shape)
    pspec_groups = part.split(pspecs, is_leaf=lambda s: isinstance(s, P))
    h_w, h_s, h_d = {}, {}, {}
    for g, gname in enumerate(part.group_names):
        cfg_g, leaves = part.configs[g], p_groups[g]
        if cfg_g.bucketed:
            dp = bucket_layout(cfg_g, leaves).padded_size
            flat_axis = "model" if msize > 1 and dp % msize == 0 else None
            h_w[gname] = NamedSharding(mesh, P(wtuple if waxes else None, flat_axis))
            h_s[gname] = NamedSharding(mesh, P(flat_axis))
        else:
            hsp = h_flat_specs(pspec_groups[g])
            h_w[gname] = [NamedSharding(mesh, P(wtuple if waxes else None, *s))
                          for s in hsp]
            h_s[gname] = [NamedSharding(mesh, s) for s in hsp]
        dcfg = part.down_configs[g]
        if dcfg is not None:
            if dcfg.bucketed:
                dpd = bucket_layout(dcfg, leaves).padded_size
                ax = "model" if msize > 1 and dpd % msize == 0 else None
                h_d[gname] = NamedSharding(mesh, P(ax))
            else:
                h_d[gname] = [NamedSharding(mesh, s)
                              for s in h_flat_specs(pspec_groups[g])]
    return DianaState(h_worker=h_w, h_server=h_s, vr=vr_shard,
                      h_down=h_d if h_d else None)


def h_flat_specs(grad_specs):
    """Per-leaf PartitionSpec for the flat DIANA memories, derived from the
    gradient specs so that each h leaf's LOCAL length equals the flattened
    local gradient shard inside the nested manual aggregation: the flat dim
    shards over the combined tuple of the leaf's sharded axes (replicated
    leaves keep replicated memories)."""

    def to_h(spec):
        axes = []
        for entry in spec:
            if entry is None:
                continue
            if isinstance(entry, tuple):
                axes.extend(entry)
            else:
                axes.append(entry)
        if not axes:
            return P(None)
        return P(tuple(axes) if len(axes) > 1 else axes[0])

    return jax.tree_util.tree_map(
        to_h, grad_specs, is_leaf=lambda s: isinstance(s, P)
    )


def _inner_shardings(inner_shape, p_shard, mesh):
    """Momentum: a params-shaped tree; AdamW: two of them + a counter; SGD: ()."""
    from repro.optim.optimizers import AdamState

    if isinstance(inner_shape, AdamState):
        return AdamState(mu=p_shard, nu=p_shard, count=NamedSharding(mesh, P()))
    if isinstance(inner_shape, tuple) and len(inner_shape) == 0:
        return ()
    return p_shard


# ---------------------------------------------------------------------------
# The step
# ---------------------------------------------------------------------------

def build_train_step(cfg, opt: DianaOptimizer, mesh, shape=None, *, window: Optional[int] = None,
                     faults=None, telemetry: bool = False):
    """Returns a jitted ``step(params, opt_state, batch, key) -> (params, opt_state, metrics)``.

    ``faults`` (a :class:`~repro.core.participation.FaultPlan`) arms the
    wire checksum on the aggregation round — corrupted payloads are detected
    and excluded (DESIGN.md §Elasticity).  Requires the flat bucketed layout
    (the checksum rides the fused uint8 wire buffer).

    ``telemetry=True`` adds the budget controller's fixed-shape per-group
    statistics to the metrics dict (``telemetry_m2`` / ``telemetry_var``,
    each ``(n_groups,)``, plus the scalar ``telemetry_ok`` degraded-step
    gate) — replicated outputs measured on already-replicated values, so no
    extra collectives and bitwise-identical params/state to the
    untelemetered step (DESIGN.md §Controller).
    """
    mesh, waxes = resolve_train_mesh(mesh, opt.policy.worker_axes)
    opt = resolve_bucketed(opt, mesh, waxes)
    # What the aggregation round runs: the policy itself.  Uniform policies
    # collapse inside core.diana to the flat config — the bitwise pre-policy
    # path; grouped policies take the grouped driver.
    comp = opt.policy
    n_workers = worker_count(mesh, waxes)

    from repro.compat import supports_nested_manual

    if waxes and not supports_nested_manual() and not cfg.scan_unroll:
        # Old XLA RET_CHECKs on dynamic-slice over scan-stacked params inside
        # any manual subgroup; statically unrolling the layer scan removes
        # the dynamic-slice (same math, bigger HLO — fine at test scale).
        from dataclasses import replace as _dc_replace

        cfg = _dc_replace(cfg, scan_unroll=True)
    daxes = data_axes(mesh)
    wtuple = waxes if len(waxes) != 1 else waxes[0]

    # Inner axes of size 1 join the manual set: on a pure worker mesh the
    # body is then fully manual, which Pallas kernels need (a Mosaic call
    # cannot be partitioned automatically).
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    manual = tuple(waxes) + tuple(a for a in mesh.axis_names
                                  if a not in waxes and sizes[a] == 1)
    inner_axes = tuple(a for a in mesh.axis_names if a not in manual)
    fsdp = tuple(a for a in daxes if a not in waxes)

    def local_step(params, opt_state, batch, key, widx):
        # widx: (1,) int32 — this worker's linear index, fed in as sharded
        # data rather than computed via axis_index (which lowers to an
        # unpartitionable PartitionId under partial-manual on old XLA).
        policy = GSPMDPolicy(mesh, manual=manual)
        with sharding_policy(policy):
            loss_fn = lambda p: train_loss(p, batch, cfg, window=window)
            loss, grads = jax.value_and_grad(loss_fn)(params)

            vr_kwargs = {}
            if opt_state.diana.vr is not None:
                # VR-DIANA: second backward at this worker's snapshot on the
                # SAME batch.  The refresh candidate for mu is the minibatch
                # gradient at x — the streaming stand-in for the finite-sum
                # mean (DESIGN.md §VR); step 0 forces a refresh so the
                # zeros-init mu never drives a whole epoch.
                snap_own = jax.tree_util.tree_map(
                    lambda s: s[0], opt_state.diana.vr.snapshot
                )
                g_snap = jax.grad(loss_fn)(snap_own)
                vr_kwargs = dict(
                    vr_aux=(g_snap, grads),
                    params_local=params,
                    vr_force_refresh=opt_state.step == 0,
                )

            down_kwargs = {}
            if opt_state.diana.h_down is not None:
                # Downlink draws are worker-INDEPENDENT (every worker decodes
                # the same broadcast): fold DOWN_FOLD into the step key
                # before the worker fold below.
                from repro.core.diana import DOWN_FOLD

                down_kwargs = dict(down_key=jax.random.fold_in(key, DOWN_FOLD))

            part_kwargs = {}
            if comp.participation is not None or faults is not None:
                # Elastic round: the participation mask is drawn from the
                # step key folded with PART_FOLD — like down_key, BEFORE the
                # worker fold below, so every worker sees the identical (n,)
                # mask.  The step counter drives the churn schedule / fault
                # plan; widx locates this worker's own bit.
                from repro.core.diana import PART_FOLD

                part_kwargs = dict(
                    part_key=jax.random.fold_in(key, PART_FOLD),
                    step=opt_state.step,
                    worker_index=widx[0],
                    faults=faults,
                )

            # Hierarchical topology: every worker of a node runs the SAME
            # inter-node DIANA round (node-leader memories), so the stream is
            # folded by NODE index — the core.diana key contract.
            nsz = comp.node_size if comp.topology == "hierarchical" else 1
            wkey = jax.random.fold_in(key, widx[0] // nsz)
            # Nested fully-manual aggregation where the toolchain supports
            # it; otherwise keep the inner axes auto (GSPMD constraints) —
            # old XLA RET_CHECKs on completing manualization in a nested map.
            from repro.compat import supports_nested_manual

            gspecs = (
                param_specs(params, cfg, mesh, fsdp_axes=fsdp)
                if supports_nested_manual() else None
            )
            agg = aggregate_shardmap(
                grads, opt_state.diana, wkey, comp,
                axis_names=waxes, n_workers=n_workers,
                inner_axes=inner_axes,
                grad_specs=gspecs,
                h_specs=h_flat_specs(gspecs) if gspecs is not None else None,
                mesh=mesh,
                telemetry=telemetry,
                **vr_kwargs,
                **down_kwargs,
                **part_kwargs,
            )
            if telemetry:
                ghat, new_diana, telem = agg
            else:
                ghat, new_diana = agg
            if waxes:
                with jax.named_scope("train.metrics"):
                    loss = jax.lax.pmean(loss, waxes)
            with jax.named_scope("train.optimizer"):
                new_params, new_opt = opt.apply_direction(params, ghat, opt_state, new_diana)
        with jax.named_scope("train.metrics"):
            gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                                 for g in jax.tree_util.tree_leaves(ghat)))
        metrics = {"loss": loss, "ghat_norm": gnorm, "step": new_opt.step}
        if telemetry:
            metrics["telemetry_m2"] = telem.m2
            metrics["telemetry_var"] = telem.var
            metrics["telemetry_ok"] = telem.ok
        return new_params, new_opt, metrics

    if not waxes:
        def single(params, opt_state, batch, key):
            return local_step(params, opt_state, batch, key,
                              jnp.zeros((1,), jnp.int32))
        return jax.jit(single, donate_argnums=(0, 1))

    # --- shard_map in/out specs: manual axes only ---
    rep = P()

    def p_spec(_):
        return rep

    def opt_spec_tree(opt_state_shape):
        dvr = opt_state_shape.diana.vr
        vr_spec = None
        if dvr is not None:
            vr_spec = VRState(
                snapshot=jax.tree_util.tree_map(lambda _: P(wtuple), dvr.snapshot),
                mu=jax.tree_util.tree_map(lambda _: P(wtuple), dvr.mu),
            )
        down_spec = None
        if opt_state_shape.diana.h_down is not None:
            down_spec = jax.tree_util.tree_map(
                lambda _: rep, opt_state_shape.diana.h_down
            )
        diana_spec = DianaState(
            h_worker=jax.tree_util.tree_map(lambda _: P(wtuple), opt_state_shape.diana.h_worker),
            h_server=jax.tree_util.tree_map(lambda _: rep, opt_state_shape.diana.h_server),
            vr=vr_spec,
            h_down=down_spec,
        )
        return DianaOptState(
            step=rep,
            inner=jax.tree_util.tree_map(lambda _: rep, opt_state_shape.inner),
            diana=diana_spec,
        )

    def batch_spec_tree(batch_shape):
        return jax.tree_util.tree_map(lambda _: P(wtuple), batch_shape)

    def wrapped(params, opt_state, batch, key):
        in_specs = (
            jax.tree_util.tree_map(p_spec, params),
            opt_spec_tree(opt_state),
            batch_spec_tree(batch),
            rep,
            P(wtuple),
        )
        mspec = {"loss": rep, "ghat_norm": rep, "step": rep}
        if telemetry:
            # Fixed-shape (n_groups,) arrays, identical on every worker —
            # replicated out-specs, no collectives.
            mspec.update(telemetry_m2=rep, telemetry_var=rep,
                         telemetry_ok=rep)
        out_specs = (
            jax.tree_util.tree_map(p_spec, params),
            opt_spec_tree(opt_state),
            mspec,
        )
        fn = shard_map(
            local_step,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=out_specs,
            axis_names=set(manual),
            check_vma=False,
        )
        return fn(params, opt_state, batch, key,
                  jnp.arange(n_workers, dtype=jnp.int32))

    return jax.jit(wrapped, donate_argnums=(0, 1))


# ---------------------------------------------------------------------------
# State init (concrete, for real runs)
# ---------------------------------------------------------------------------

def init_train_state(cfg, opt: DianaOptimizer, mesh, key):
    smesh, rwaxes = resolve_train_mesh(mesh, opt.policy.worker_axes)
    opt = resolve_bucketed(opt, smesh, rwaxes)
    waxes = worker_axes_in(mesh, opt.policy.worker_axes)
    n_workers = worker_count(mesh, waxes)

    params_shape = jax.eval_shape(lambda k: init_model(cfg, k), key)
    opt_state_shape = jax.eval_shape(lambda p: opt.init(p, n_workers), params_shape)
    p_shard, o_shard = train_state_shardings(cfg, opt, mesh, params_shape, opt_state_shape)

    params = jax.jit(lambda k: init_model(cfg, k), out_shardings=p_shard)(key)
    opt_state = jax.jit(lambda p: opt.init(p, n_workers), out_shardings=o_shard)(params)
    return params, opt_state, (p_shard, o_shard)


# ---------------------------------------------------------------------------
# CLI driver
# ---------------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description="DIANA distributed trainer")
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--inner", default="momentum", choices=["momentum", "adamw"])
    from repro.core import available_methods

    ap.add_argument("--compression", default=None,
                    choices=[None, *available_methods()])
    ap.add_argument("--comp-k", type=int, default=None,
                    help="kept coordinates for rand-k / top-k compressors")
    ap.add_argument("--down-method", default=None,
                    choices=[None, *available_methods()],
                    help="compress the server->worker broadcast too "
                         "(bidirectional DIANA): any registry operator, with "
                         "its own downlink memory h_down; default keeps the "
                         "broadcast full-precision")
    ap.add_argument("--down-k", type=int, default=None,
                    help="kept coordinates for a sparse downlink operator "
                         "(default: --comp-k)")
    ap.add_argument("--comp-policy", default=None,
                    help="per-parameter-group compression policy: a policy "
                         ".json file, inline rules "
                         "(pattern=method[:opt=v...][/down_method...],...; "
                         "'*' = catch-all), 'default' for the model's "
                         "curated ModelConfig.comp_policy, or "
                         "'size-adaptive' (small leaves dense, the bulk "
                         "quantized — CompressionPolicy.size_adaptive).  "
                         "Overrides the flat "
                         "--compression/--comp-k/--down-* surface")
    ap.add_argument("--budget-bits-per-dim", type=float, default=None,
                    help="enable the adaptive bit-budget controller: target "
                         "mean uplink wire cost (bits per model coordinate "
                         "per step, policy_bits_per_dim units).  Telemetry "
                         "EMAs per policy group feed an allocator that "
                         "re-picks each group's operator from a finite "
                         "candidate lattice, keeping every emitted policy "
                         "at or under this budget (DESIGN.md §Controller)")
    ap.add_argument("--controller-interval", type=int, default=50,
                    help="controller dwell window: at most one policy "
                         "switch per this many steps (hysteresis guards "
                         "near-ties)")
    ap.add_argument("--warmup-dense-steps", type=int, default=0,
                    help="run the first N steps with dense (identity) "
                         "aggregation on the same policy skeleton before "
                         "the first budget allocation — exact gradients "
                         "while the loss surface is steep")
    ap.add_argument("--chunk-bytes", type=int, default=None,
                    help="split the bucketed wire into ~this many bytes per "
                         "chunk (ChunkedSchedule): chunk i+1's all-gather is "
                         "issued before chunk i's decode so communication "
                         "overlaps decode work.  0/default keeps the "
                         "monolithic single-chunk wire; results are bitwise "
                         "identical either way")
    ap.add_argument("--topology", default=None,
                    choices=[None, "flat", "hierarchical"],
                    help="aggregation topology: 'flat' (default) exchanges "
                         "compressed payloads between all workers; "
                         "'hierarchical' runs an uncompressed intra-node "
                         "mean first, then the compressed DIANA exchange "
                         "between node leaders (h kept per node, so "
                         "h == mean(h_i) holds exactly).  Bucketed only")
    ap.add_argument("--node-size", type=int, default=None,
                    help="workers per node for --topology hierarchical "
                         "(must divide the worker count; inferred from a "
                         "'node' mesh axis when present)")
    ap.add_argument("--per-leaf-agg", action="store_true",
                    help="disable the bucketed (flat-buffer) aggregation and "
                         "compress/gather/decode each parameter leaf separately")
    ap.add_argument("--vr", action="store_true",
                    help="VR-DIANA (arXiv:1904.05115): per-worker L-SVRG "
                         "control variates under the compressed-difference "
                         "loop (one extra backward pass per step)")
    ap.add_argument("--vr-p", type=float, default=None,
                    help="L-SVRG snapshot-refresh probability; default is the "
                         "paper's 1/m with m = the per-worker batch size")
    ap.add_argument("--participation-q", type=float, default=None,
                    help="elastic rounds: independent per-worker sampling "
                         "probability q (partial participation; the masked "
                         "sum is rescaled to stay unbiased).  Default 1.0 "
                         "keeps the exact pre-elastic path")
    ap.add_argument("--participation-dropout", type=float, default=None,
                    help="straggler model: probability a sampled worker "
                         "misses the round deadline and is dropped (its "
                         "DIANA memory freezes; the rescale stays unbiased)")
    ap.add_argument("--min-workers", type=int, default=None,
                    help="degraded-step floor: with fewer than this many "
                         "participants the round applies no update (ghat=0, "
                         "all state frozen) instead of a high-variance step")
    ap.add_argument("--faults", default=None,
                    help="fault-injection plan: ';'-separated "
                         "'kind:step=S,worker=W[,byte=B|delay=D]' events with "
                         "kind in {drop,delay,corrupt} (e.g. "
                         "'corrupt:step=3,worker=1'), or the bare word "
                         "'checksum' to arm the wire checksum with no "
                         "injected faults.  Requires the bucketed layout")
    ap.add_argument("--mesh", default=None, help="e.g. 2x2 (data x model) or 2x2x2")
    ap.add_argument("--reduced", action="store_true", help="toy config for CPU runs")
    ap.add_argument("--batch", type=int, default=None, help="override global batch")
    ap.add_argument("--seq", type=int, default=None, help="override sequence length")
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds the weights, the synthetic batches and the "
                         "compression draws")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--profile-dir", default=None,
                    help="write a JAX profiler trace of the steps that "
                         "--profile-steps names into this directory "
                         "(README 'Profiling a training run')")
    ap.add_argument("--profile-steps", default="1:4",
                    help="the traced steps as a:b, step a up to but not "
                         "including step b (used with --profile-dir)")
    args = ap.parse_args(argv)
    profile = _profile_window(args.profile_dir, args.profile_steps, args.steps)

    from dataclasses import replace as dc_replace

    from repro.configs import reduced as make_reduced
    from repro.configs.base import ShapeConfig
    from repro.data import make_lm_batch

    from .compile_cache import use_compile_cache

    use_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = make_reduced(cfg)
    if args.compression:
        cfg = dc_replace(cfg, compression=args.compression)
    if args.comp_k:
        cfg = dc_replace(cfg, comp_k=args.comp_k)
    if args.down_method:
        cfg = dc_replace(cfg, comp_down_method=args.down_method)
    if args.down_k:
        cfg = dc_replace(cfg, comp_down_k=args.down_k)
    if args.per_leaf_agg:
        cfg = dc_replace(cfg, comp_bucketed=False)
    shape = get_shape(args.shape)
    if args.batch or args.seq:
        shape = ShapeConfig(shape.name, args.seq or shape.seq_len,
                            args.batch or shape.global_batch, shape.kind)

    if args.mesh:
        dims = tuple(int(x) for x in args.mesh.split("x"))
        # Under --topology hierarchical a 3-dim mesh is (node, data, model):
        # the leading axis marks the node boundary the two-level round uses.
        axes = (("node", "data", "model") if args.topology == "hierarchical"
                and len(dims) == 3 else ("pod", "data", "model"))[-len(dims):]
        mesh = make_mesh(dims, axes)
    else:
        mesh = make_mesh((jax.device_count(), 1), ("data", "model"))

    if args.vr:
        smesh0, waxes0 = resolve_train_mesh(mesh, cfg.comp_worker_axes)
        m_local = max(1, shape.global_batch // max(worker_count(smesh0, waxes0), 1))
        cfg = dc_replace(cfg, vr=True,
                         vr_p=resolve_vr_p(args.vr_p, m_local))

    participation = None
    if (args.participation_q is not None or args.participation_dropout is not None
            or args.min_workers is not None):
        from repro.core.participation import ParticipationSpec

        participation = ParticipationSpec(
            q=1.0 if args.participation_q is None else args.participation_q,
            dropout=args.participation_dropout or 0.0,
            min_workers=args.min_workers or 1,
        )
    from repro.core.participation import parse_faults

    faults = parse_faults(args.faults)
    if faults is not None and (args.per_leaf_agg or not cfg.comp_bucketed
                               or args.comp_policy):
        raise SystemExit("--faults needs the flat bucketed layout (the "
                         "checksum rides the fused wire buffer)")

    opt = make_optimizer(cfg, lr=args.lr, inner=args.inner,
                         policy=args.comp_policy, participation=participation)
    if args.chunk_bytes is not None or args.topology or args.node_size:
        pol = opt.policy
        node_size = args.node_size or pol.node_size
        topology = args.topology or pol.topology
        waxes_pol = pol.worker_axes
        if topology == "hierarchical" and "node" in mesh.axis_names:
            # A 'node' worker mesh axis declares the node boundary: it joins
            # the worker axes (leading, so resolve_train_mesh flattens
            # node-major) and the workers of one node are the contiguous
            # non-'node' remainder.
            if "node" not in waxes_pol:
                waxes_pol = ("node",) + tuple(waxes_pol)
            if args.node_size is None:
                node_size = (worker_count(mesh, waxes_pol)
                             // mesh.shape["node"])
        opt = opt.replace(policy=pol.replace(
            chunk_bytes=pol.chunk_bytes if args.chunk_bytes is None
            else args.chunk_bytes,
            topology=topology, node_size=node_size,
            worker_axes=waxes_pol))
    controller = None
    if args.budget_bits_per_dim is not None:
        from repro.core import BudgetController

        if faults is not None:
            raise SystemExit("--budget-bits-per-dim does not compose with "
                             "--faults (the checksum budget tail depends on "
                             "the fault plan, not the policy)")
        # The author's (possibly uniform) policy is the controller skeleton;
        # every emitted policy is a with_rule_specs mutation of it.
        controller = BudgetController(
            base=opt.policy,
            budget_bits_per_dim=args.budget_bits_per_dim,
            interval=args.controller_interval,
            warmup_dense_steps=args.warmup_dense_steps,
        )
        if args.warmup_dense_steps > 0:
            opt = opt.replace(policy=controller.warmup_policy())

    key = jax.random.PRNGKey(args.seed)
    params, opt_state, _ = init_train_state(cfg, opt, mesh, key)
    step_fn = build_train_step(cfg, opt, mesh, shape, faults=faults,
                               telemetry=controller is not None)
    smesh, _ = resolve_train_mesh(mesh, opt.policy.worker_axes)

    cstate = None
    step_cache = {}
    if controller is not None:
        from repro.core import init_controller_state

        cstate = init_controller_state(controller, params)
        step_cache[opt.policy] = (opt, step_fn)

    def device_batch(step):
        host_batch = make_lm_batch(cfg, shape, step, seed=args.seed)
        return jax.tree_util.tree_map(
            lambda a, s: jax.device_put(a, NamedSharding(smesh, s)),
            host_batch, batch_specs(host_batch, smesh))

    # Compile before the first step, so that compilation is set-up time and
    # every step below runs the already compiled program.
    t0 = time.perf_counter()
    compiled = step_fn.lower(params, opt_state, device_batch(0),
                             jax.random.fold_in(key, 0)).compile()
    compile_s = time.perf_counter() - t0
    print(f"compiled the step in {compile_s:.2f}s")

    # Host spans for the profiler (``train.*``, one StepTraceAnnotation per
    # step); they cost nothing and write nothing while no trace is active.
    losses, ghat_norms, step_s = [], [], []
    tracing = False
    try:
        for step in range(args.steps):
            if profile is not None and step == profile.start:
                jax.profiler.start_trace(args.profile_dir)
                tracing = True
            if tracing and step == profile.stop:
                jax.profiler.stop_trace()
                tracing = False
            with StepTraceAnnotation("train", step_num=step):
                with TraceAnnotation("train.feed"):
                    batch = device_batch(step)
                t0 = time.perf_counter()
                with TraceAnnotation("train.step"):
                    params, opt_state, metrics = step_fn(
                        params, opt_state, batch, jax.random.fold_in(key, step))
                with TraceAnnotation("train.block"):
                    jax.block_until_ready((params, opt_state, metrics))
                step_s.append(time.perf_counter() - t0)
                losses.append(float(metrics["loss"]))
                ghat_norms.append(float(metrics["ghat_norm"]))
                print(f"step {step:4d} loss {losses[-1]:8.4f} ghat {ghat_norms[-1]:9.4f} "
                      f"({step_s[-1]:5.2f}s)")

                if controller is not None:
                    with TraceAnnotation("train.controller"):
                        opt, opt_state, step_fn, cstate = _controller_tick(
                            cfg, controller, cstate, opt, opt_state, step_fn, metrics,
                            params, mesh, shape, step_cache)

        if args.checkpoint_dir:
            from repro.checkpoint import save_checkpoint

            # The policy rides in the manifest metadata so a restore can rebuild
            # the matching (possibly grouped) state template without the CLI
            # args; with the controller on, its state + telemetry EMAs ride
            # along so a resume keeps the dwell clock and learned statistics
            # (repro.checkpoint.controller_restore_hint flags pre-controller
            # checkpoints).
            metadata = {"policy": opt.policy.to_json_dict()}
            if controller is not None:
                from repro.core import controller_metadata

                metadata["controller"] = controller_metadata(controller, cstate)
            with TraceAnnotation("train.checkpoint"):
                save_checkpoint(args.checkpoint_dir, args.steps, {"params": params},
                                metadata=metadata)
            print(f"checkpoint written to {args.checkpoint_dir}")
    finally:
        if tracing:
            jax.profiler.stop_trace()
    if profile is not None:
        print(f"profiler trace of steps {profile.start}-{profile.stop - 1} "
              f"written under {args.profile_dir}")
    return TrainRun(compiled, compile_s, losses, ghat_norms, step_s)


def _profile_window(profile_dir: Optional[str], steps: str,
                    n_steps: int) -> Optional[range]:
    """The steps ``--profile-steps a:b`` names (``range(a, b)``), or None
    without ``--profile-dir``."""
    if profile_dir is None:
        return None
    try:
        a, b = (int(x) for x in steps.split(":"))
    except ValueError:
        raise SystemExit(f"--profile-steps wants a:b, got {steps!r}") from None
    if not 0 <= a < min(b, n_steps):
        raise SystemExit(f"--profile-steps {steps}: needs 0 <= a < b and a < --steps")
    return range(a, min(b, n_steps))


class TrainRun(NamedTuple):
    """What :func:`main` ran: the compiled first-step program, its compile
    time, and per step the loss, the served direction's norm and the wall
    time (host clock, after ``block_until_ready``)."""

    compiled: Any
    compile_s: float
    losses: list
    ghat_norms: list
    step_s: list


def _controller_tick(cfg, controller, cstate, opt, opt_state, step_fn,
                     metrics, params, mesh, shape, step_cache):
    """One post-step controller turn: fold the step's telemetry into the
    EMAs, ask the allocator for a (dwell/hysteresis-gated) decision, and on
    a switch migrate the DIANA memories onto the new policy's layout and
    swap in the (cached) compiled step.

    The per-policy step cache is the jit-cache-warmness story: candidates
    come from a finite lattice, so revisiting an allocation reuses its
    compiled step instead of recompiling (DESIGN.md §Controller).
    """
    from repro.core import (
        GroupTelemetry, maybe_reallocate, migrate_diana_state, observe,
        policy_bits_per_dim,
    )

    telem = GroupTelemetry(m2=metrics["telemetry_m2"],
                           var=metrics["telemetry_var"],
                           ok=metrics["telemetry_ok"])
    cstate = observe(controller, cstate, telem)
    cstate, new_policy = maybe_reallocate(controller, cstate, params)
    if new_policy is None or new_policy == opt.policy:
        return opt, opt_state, step_fn, cstate

    print(f"controller: switching policy at step {cstate.step} "
          f"(bits/dim {policy_bits_per_dim(new_policy, params):.3f} <= "
          f"budget {controller.budget_bits_per_dim})")
    if new_policy in step_cache:
        opt, step_fn = step_cache[new_policy]
    else:
        opt = opt.replace(policy=new_policy)
        step_fn = build_train_step(cfg, opt, mesh, shape, telemetry=True)
        step_cache[new_policy] = (opt, step_fn)

    smesh, waxes = resolve_train_mesh(mesh, opt.policy.worker_axes)
    n_workers = worker_count(smesh, waxes)
    # Migrate onto the RESOLVED layout (resolve_bucketed may downgrade) —
    # init and step must agree on the state template.
    resolved_policy = resolve_bucketed(opt, smesh, waxes).policy
    new_diana = migrate_diana_state(opt_state.diana, params, resolved_policy,
                                    n_workers)
    opt_state = DianaOptState(step=opt_state.step, inner=opt_state.inner,
                              diana=new_diana)
    params_shape = jax.eval_shape(lambda p: p, params)
    opt_state_shape = jax.eval_shape(lambda s: s, opt_state)
    _, o_shard = train_state_shardings(cfg, opt, mesh, params_shape,
                                       opt_state_shape)
    opt_state = jax.device_put(opt_state, o_shard)
    return opt, opt_state, step_fn, cstate


if __name__ == "__main__":
    main()
