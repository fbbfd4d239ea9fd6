"""Fused PQ ADC expand: asymmetric-distance gather-accumulate + mask +
C_pca threshold + kSort.L in a single VMEM residency.

The PQ filter's expansion step mirrors ``fused_filter.fused_expand``
with the dense low-dim Dist.L replaced by ADC: each neighbor carries
S uint8 codes, the query carries a per-subspace lookup table
``lut[S, 256]`` built once per query, and the filter distance is
``sum_s lut[s, codes[s]]``. TPUs have no VMEM gather, so the kernel
scores codes with a one-hot contraction against the 256 centroid slots
(`codes == iota(256)`), which is pure VPU element-wise work — the same
formulation trick as the comparison-matrix kSort.L (DESIGN.md). The
0.0-masked lanes never perturb an f32 sum, so the kernel matches the
gathering oracle (``ref.pq_adc_ref``) up to f32 summation order —
bit-equal on exactly-representable table values (asserted in
tests/test_kernels.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.constants import INF
from repro.kernels.fused_filter import ksort_block


def _pq_adc_expand_kernel(codes_ref, lut_ref, valid_ref, th_ref,
                          val_ref, idx_ref, *, k: int):
    codes = codes_ref[...].astype(jnp.int32)             # [bb, M, S]
    lut = lut_ref[...].astype(jnp.float32)               # [bb, S, 256]
    valid = valid_ref[...] != 0                          # [bb, M]
    th = th_ref[...].astype(jnp.float32)                 # [bb, 1]
    # -- ADC: one-hot gather-accumulate over the 256 centroid slots --
    cc = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, 256), 3)
    onehot = codes[:, :, :, None] == cc                  # [bb, M, S, 256]
    # centroid slots first, then subspaces: Mosaic reduces over both
    # trailing dims at once only into a trailing axis of size 1
    d = jnp.sum(jnp.sum(jnp.where(onehot, lut[:, None, :, :], 0.0),
                        axis=3), axis=2)                 # [bb, M]
    d = jnp.where(valid & (d < th), d, INF)              # filter
    val_ref[...], idx_ref[...] = ksort_block(d, k)       # kSort.L


def pq_adc_expand_pallas(codes, lut, valid, th, k: int, *,
                         block_b: int = 8, interpret: bool = False):
    """codes: [B, M, S] int32; lut: [B, S, 256] f32; valid: [B, M] int32
    (0/1); th: [B, 1] f32 -> (vals [B, k] ascending, idx [B, k]).
    Non-survivors get vals = INF."""
    B, M, S = codes.shape
    assert B % block_b == 0, (B, block_b)
    assert lut.shape == (B, S, 256), (lut.shape, codes.shape)
    kernel = lambda cr, lr, vr, tr, or_, ir: \
        _pq_adc_expand_kernel(cr, lr, vr, tr, or_, ir, k=k)
    return pl.pallas_call(
        kernel,
        grid=(B // block_b,),
        in_specs=[
            pl.BlockSpec((block_b, M, S), lambda i: (i, 0, 0)),
            pl.BlockSpec((block_b, S, 256), lambda i: (i, 0, 0)),
            pl.BlockSpec((block_b, M), lambda i: (i, 0)),
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
        ],
        out_specs=(
            pl.BlockSpec((block_b, k), lambda i: (i, 0)),
            pl.BlockSpec((block_b, k), lambda i: (i, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((B, k), jnp.float32),
            jax.ShapeDtypeStruct((B, k), jnp.int32),
        ),
        interpret=interpret,
    )(codes, lut, valid, th)
