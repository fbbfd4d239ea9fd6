"""Public jit'd wrappers for the Pallas kernels.

Padding, block-size selection, and backend dispatch live here. On a TPU
the kernels run compiled by Mosaic; elsewhere they run as their jnp
oracles (``kernels/ref.py``) by default, or under ``interpret=True``
when asked (the kernel body executes in Python on CPU — bit-faithful
semantics, no performance claim). ``kernel_path`` is the one place
that decides.
"""
from __future__ import annotations

import functools
import os
import warnings

import jax
import jax.numpy as jnp

from repro.constants import VALID_MAX  # noqa: F401  (re-export: callers
# of fused_expand test returned vals against this sentinel)
from repro.kernels import ref
from repro.kernels.dist_l import dist_l_pallas
from repro.kernels.ksort_l import ksort_l_pallas
from repro.kernels.dist_h import dist_h_pallas
from repro.kernels.fused_filter import fused_expand_pallas, fused_filter_pallas
from repro.kernels.merge_sorted import merge_sorted_pallas
from repro.kernels.pq_adc import pq_adc_expand_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.decode_attention import decode_attention_pallas


def kernel_path() -> str:
    """What the kernel wrappers trace to right now: "compiled" (Pallas
    lowered by Mosaic, TPU only), "interpret" (Pallas interpret mode) or
    "ref" (the jnp oracles). Off the TPU the oracles serve by default:
    interpret mode runs the kernel body in Python per grid step (~100x
    slower). ``REPRO_FORCE_PALLAS_INTERPRET=1`` forces interpret mode
    (the kernel test suite uses it); ``REPRO_KERNEL_IMPL=ref`` forces
    the oracles and ``=pallas`` the kernels. Read at trace time —
    ``jax.clear_caches()`` between switches in one process. On a TPU a
    forced non-compiled path warns, since no deployment runs it."""
    on_tpu = jax.default_backend() == "tpu"
    impl = os.environ.get("REPRO_KERNEL_IMPL", "auto")
    if os.environ.get("REPRO_FORCE_PALLAS_INTERPRET"):
        path = "interpret"
    elif impl == "ref":
        path = "ref"
    elif on_tpu:
        path = "compiled"
    else:
        path = "interpret" if impl == "pallas" else "ref"
    if on_tpu and path != "compiled":
        warnings.warn(f"Pallas kernels traced as {path!r} on a TPU "
                      "(REPRO_FORCE_PALLAS_INTERPRET or REPRO_KERNEL_IMPL "
                      "is set)", RuntimeWarning, stacklevel=2)
    return path


def _pad_batch(x, mult: int):
    B = x.shape[0]
    pad = (-B) % mult
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])
    return x, B


def _pick_block_b(B: int) -> int:
    """Batch rows per grid step. Mosaic tiles the second-to-last dim of
    the 2-D operand blocks ([bb, M], [bb, k], [bb, 1]) in sublanes of 8,
    so a block is 8 rows — or the whole batch when it has fewer (a block
    equal to the full dim is always legal). Larger batches pad up to a
    multiple of 8. The per-row VMEM footprint never shrinks the block
    below that: at the real widths (M0=32, S=16, D<=960) 8 rows fit
    (tests/test_tpu_compile.py)."""
    return min(B, 8)


@jax.jit
def dist_l(x, q):
    """x: [B, M, dl]; q: [B, dl] -> [B, M] f32 squared distances."""
    path = kernel_path()
    if path == "ref":
        return ref.dist_l_ref(x, q)
    bb = _pick_block_b(x.shape[0])
    xp, B = _pad_batch(x, bb)
    qp, _ = _pad_batch(q, bb)
    return dist_l_pallas(xp, qp, block_b=bb,
                         interpret=path == "interpret")[:B]


@functools.partial(jax.jit, static_argnames=("k",))
def ksort_l(d, k: int):
    """d: [B, M] -> (vals [B, k] ascending, idx [B, k])."""
    path = kernel_path()
    if path == "ref":
        return ref.ksort_l_ref(d, k)
    bb = _pick_block_b(d.shape[0])
    dp, B = _pad_batch(d, bb)
    v, i = ksort_l_pallas(dp, k, block_b=bb,
                          interpret=path == "interpret")
    return v[:B], i[:B]


@jax.jit
def dist_h(x, q):
    """x: [B, K, D]; q: [B, D] -> [B, K] f32 squared distances."""
    path = kernel_path()
    if path == "ref":
        return ref.dist_h_ref(x, q)
    bb = _pick_block_b(x.shape[0])
    xp, B = _pad_batch(x, bb)
    qp, _ = _pad_batch(q, bb)
    return dist_h_pallas(xp, qp, block_b=bb,
                         interpret=path == "interpret")[:B]


@functools.partial(jax.jit, static_argnames=("k",))
def fused_filter(x, q, k: int):
    """pHNSW step 2: x [B, M, dl], q [B, dl] -> top-k (vals, idx)."""
    path = kernel_path()
    if path == "ref":
        return ref.fused_filter_ref(x, q, k)
    bb = _pick_block_b(x.shape[0])
    xp, B = _pad_batch(x, bb)
    qp, _ = _pad_batch(q, bb)
    v, i = fused_filter_pallas(xp, qp, k, block_b=bb,
                               interpret=path == "interpret")
    return v[:B], i[:B]


@functools.partial(jax.jit, static_argnames=("k",))
def fused_expand(x, q, valid, th, k: int):
    """One traversal expansion's full filter stage (Dist.L + validity
    mask + C_pca threshold + kSort.L) in a single kernel.
    x: [B, M, dl]; q: [B, dl]; valid: [B, M] bool; th: [B] f32.
    Returns (vals [B, k] ascending, idx [B, k]); filtered-out slots get
    vals >= constants.VALID_MAX."""
    path = kernel_path()
    if path == "ref":
        return ref.fused_expand_ref(x, q, valid, th, k)
    bb = _pick_block_b(x.shape[0])
    xp, B = _pad_batch(x, bb)
    qp, _ = _pad_batch(q, bb)
    vp, _ = _pad_batch(valid.astype(jnp.int32), bb)
    tp, _ = _pad_batch(th[:, None].astype(jnp.float32), bb)
    v, i = fused_expand_pallas(xp, qp, vp, tp, k, block_b=bb,
                               interpret=path == "interpret")
    return v[:B], i[:B]


@functools.partial(jax.jit, static_argnames=("k",))
def pq_adc_expand(codes, lut, valid, th, k: int):
    """One traversal expansion's PQ filter stage (ADC gather-accumulate
    + validity mask + C_pca threshold + kSort.L) in a single kernel —
    the PQ analogue of ``fused_expand``.
    codes: [B, M, S] integer PQ codes; lut: [B, S, 256] f32; valid:
    [B, M] bool; th: [B] f32. Returns (vals [B, k] ascending, idx
    [B, k]); filtered-out slots get vals >= constants.VALID_MAX."""
    path = kernel_path()
    if path == "ref":
        return ref.pq_adc_expand_ref(codes, lut, valid, th, k)
    B, M, S = codes.shape
    bb = _pick_block_b(B)
    cp, _ = _pad_batch(codes.astype(jnp.int32), bb)
    lp, _ = _pad_batch(lut.astype(jnp.float32), bb)
    vp, _ = _pad_batch(valid.astype(jnp.int32), bb)
    tp, _ = _pad_batch(th[:, None].astype(jnp.float32), bb)
    v, i = pq_adc_expand_pallas(cp, lp, vp, tp, k, block_b=bb,
                                interpret=path == "interpret")
    return v[:B], i[:B]


@jax.jit
def pq_adc(codes, lut):
    """Plain batched ADC distances (no mask/sort): codes [B, K, S],
    lut [B, S, 256] -> [B, K] f32. Used for entry-point scoring in
    deferred-rerank traversal; tiny, so it always runs the jnp oracle."""
    return ref.pq_adc_ref(codes, lut)


@functools.partial(jax.jit, static_argnames=("k",))
def merge_topk_sorted(d_a, i_a, d_b, i_b, k: int):
    """Merge two ascending-sorted (dist, idx) lists, keep the k smallest
    (ties -> a side, then lower slot). d_a: [B, Na]; d_b: [B, Nb]."""
    if d_b.shape[1] > k:
        # only the first k of a sorted b can reach a k-wide output
        d_b, i_b = d_b[:, :k], i_b[:, :k]
    path = kernel_path()
    if path == "ref":
        return ref.merge_topk_sorted_ref(d_a, i_a, d_b, i_b, k)
    Na, Nb = d_a.shape[1], d_b.shape[1]
    bb = _pick_block_b(d_a.shape[0])
    dap, B = _pad_batch(d_a, bb)
    iap, _ = _pad_batch(i_a, bb)
    dbp, _ = _pad_batch(d_b, bb)
    ibp, _ = _pad_batch(i_b, bb)
    v, i = merge_sorted_pallas(dap, iap, dbp, ibp, k, block_b=bb,
                               interpret=path == "interpret")
    return v[:B], i[:B]


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    bq: int = 128, bk: int = 128):
    """q: [B, H, S, d]; k, v: [B, H, T, d] -> [B, H, S, d]."""
    path = kernel_path()
    if path == "ref":
        return ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  bq=bq, bk=bk,
                                  interpret=path == "interpret")


@functools.partial(jax.jit, static_argnames=("bk",))
def decode_attention(q, k, v, length, *, bk: int = 512):
    """q: [B, H, d]; k, v: [B, H, T, d]; length [B] -> [B, H, d]."""
    path = kernel_path()
    if path == "ref":
        return ref.decode_attention_ref(q, k, v, length)
    return decode_attention_pallas(q, k, v, length, bk=bk,
                                   interpret=path == "interpret")


# re-export the oracles for tests/benchmarks
refs = ref
