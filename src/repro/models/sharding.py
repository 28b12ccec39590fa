"""Sharding-policy context: decouples model code from the runtime mode.

Model code annotates activations/params with *logical* axes via
``shard(x, "batch", None, "model")``; the active policy translates that into a
``with_sharding_constraint`` (or a no-op on a single device / in unit tests).

Three policies:
* ``NoopPolicy``       — default (CPU tests, examples).
* ``GSPMDPolicy``      — full-auto jit (serve_step, dryrun): every logical axis
                         maps to mesh axes present in the mesh.
* ``GSPMDPolicy(manual=...)`` — inside a ``shard_map`` whose manual axes are the
                         DIANA worker axes: logical axes that resolve to manual
                         mesh axes are dropped (the dimension is already local).
"""

from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = ["shard", "shard_spec", "arrays_are_local", "sharding_policy", "GSPMDPolicy", "current_policy", "LOGICAL_RULES"]

# Logical axis -> mesh axes. 'batch' spans the data axes — including the
# optional 'node' axis of the hierarchical aggregation topology (a worker
# axis like 'pod'/'data', marking the intra-node boundary; DESIGN.md
# §Topology); tensors sharded over 'model' use the logical name 'model';
# 'seq' is used by long-context decode caches (sequence parallelism);
# 'expert' by expert-parallel MoE.
LOGICAL_RULES: Dict[str, Tuple[str, ...]] = {
    "batch": ("pod", "node", "data"),
    "model": ("model",),
    "expert": ("model",),
    "seq": ("pod", "node", "data"),
    "fsdp": ("data",),
}


class _Policy:
    def apply(self, x, *logical):
        return x

    def spec(self, *logical) -> Optional[P]:
        return None


class NoopPolicy(_Policy):
    pass


class GSPMDPolicy(_Policy):
    def __init__(self, mesh, manual: Sequence[str] = (), rules: Dict[str, Tuple[str, ...]] = None):
        self.mesh = mesh
        self.manual = frozenset(manual)
        self.rules = dict(LOGICAL_RULES, **(rules or {}))

    def _resolve(self, logical):
        """Logical names -> PartitionSpec over available, non-manual mesh axes."""
        axis_names = set(self.mesh.axis_names)
        out = []
        for name in logical:
            if name is None:
                out.append(None)
                continue
            axes = tuple(
                a for a in self.rules.get(name, ())
                if a in axis_names and a not in self.manual
            )
            out.append(axes if len(axes) > 1 else (axes[0] if axes else None))
        # trim trailing Nones (cosmetic)
        while out and out[-1] is None:
            out.pop()
        return P(*out)

    def spec(self, *logical):
        return self._resolve(logical)

    def apply(self, x, *logical):
        spec = self._resolve(logical)
        if all(s is None for s in spec):
            return x
        # Inside a shard_map body the constraint must reference the tracing
        # context's ABSTRACT mesh (whose manual axes carry Manual axis types);
        # the concrete mesh is only valid at the jit boundary.
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(_context_mesh(self.mesh), spec))


def _context_mesh(mesh):
    """The tracing context's abstract mesh when there is one, else ``mesh``."""
    amesh = jax.sharding.get_abstract_mesh()
    return mesh if amesh.empty else amesh


_tls = threading.local()


def current_policy() -> _Policy:
    return getattr(_tls, "policy", None) or NoopPolicy()


@contextlib.contextmanager
def sharding_policy(policy: _Policy):
    prev = getattr(_tls, "policy", None)
    _tls.policy = policy
    try:
        yield
    finally:
        _tls.policy = prev


def shard(x, *logical):
    """Annotate array ``x`` with logical axes (no-op without a policy)."""
    return current_policy().apply(x, *logical)


def shard_forced(x, *logical):
    """Like :func:`shard` but ALWAYS applies the constraint, including
    explicit replication for None dims.  Used where XLA's sharding
    propagation makes partitioner-crashing choices (MoE dispatch under
    manual subgroups) — every intermediate is pinned."""
    policy = current_policy()
    if not isinstance(policy, GSPMDPolicy):
        return x
    spec = policy.spec(*logical)
    full = P(*(tuple(spec) + (None,) * (x.ndim - len(spec))))
    mesh = _context_mesh(policy.mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, full))


def shard_replicated(x):
    """FORCE replication (an explicit P() constraint, unlike shard(x, None...)
    which is a no-op).  Used on small per-layer vectors (norm scales etc.)
    whose scan-sliced stacked form the propagation otherwise mis-shards,
    tripping the SPMD partitioner under multiple manual axes."""
    policy = current_policy()
    if not isinstance(policy, GSPMDPolicy):
        return x
    mesh = _context_mesh(policy.mesh)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*((None,) * x.ndim)))
    )


def arrays_are_local() -> bool:
    """True where model code holds each array whole on its device: no policy,
    or every mesh axis of size > 1 is manual.  A Pallas call cannot be
    partitioned automatically, so model code takes a kernel only here."""
    policy = current_policy()
    if not isinstance(policy, GSPMDPolicy):
        return True
    return all(n == 1 or a in policy.manual for a, n in policy.mesh.shape.items())


def shard_spec(*logical) -> Optional[P]:
    return current_policy().spec(*logical)
