"""Pallas TPU kernels for DIANA's compression hot path.

quantize_pack:  fused block p-quantize + 2-bit pack (one HBM->VMEM pass)
unpack_reduce:  streaming ternary decode + accumulate over workers, with
                fused ``_mean`` / ``_apply`` (server memory update) variants
nat_pack:       natural-compression encode via exponent bit masks, plus the
                matching streaming decode_sum(+apply)
sparse:         rand-k / top-k value gather and scatter-add decode_sum(+apply)
dense:          identity payload pass-through and accumulate
ssd:            the model's Mamba-2 chunked SSD scan, forward and backward
                (not a compressor kernel: ``models/mamba2.py`` calls it;
                checked against its oracle to bf16 rounding, not bitwise)

Each kernel has a pure-jnp oracle in :mod:`ref` and is validated bitwise with
``interpret=True`` in ``tests/test_kernels.py`` / ``tests/test_kernel_coverage.py``;
``tools/check_kernels.py`` lints that every registry operator declares its
kernel capability and names its oracle.
"""

from . import dense, nat_pack, ops, ref, sparse, ssd
from .quantize_pack import quantize_pack, quantize_pack_prng
from .unpack_reduce import unpack_reduce, unpack_reduce_apply, unpack_reduce_mean

__all__ = [
    "dense",
    "nat_pack",
    "ops",
    "ref",
    "sparse",
    "ssd",
    "quantize_pack",
    "quantize_pack_prng",
    "unpack_reduce",
    "unpack_reduce_apply",
    "unpack_reduce_mean",
]
