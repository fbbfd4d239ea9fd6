"""The plain reference and the comparison that decides ``correct``.

Every distance is the deployment's ``metric`` (``spec.METRICS``):
``l2`` squared L2; ``cosine`` 1 - q.x / (|q| |x|); ``ip`` 1 - q.x.
Each is a distance (smaller is nearer), so answers come in ascending
order under all three; ``ip``'s may be negative.

``exact_topk`` is copied from ``chip_smoke.py`` (a blocked matmul and
``lax.top_k`` on the device, sharing no code with the engine), blocked
over queries as well so that a whole window's queries fit, and with the
operand dtype as a parameter: float32 at HIGHEST precision is the
reference, bfloat16 operands are the control (``control.py``). Under
``cosine`` both operands are normalised in float32 first. The distances
of the returned ids are recomputed on the host in float64.

The comparison takes every answer a run produced and holds it to the
guarantees the deployment's file states:

* ``unanswered``: requests submitted that never came back (shed, or
  still out a minute past the window or the warm-up) -- limit 0;
* ``bad_rows``: answers with fewer than k ids, an id out of range, a
  repeated id, or distances out of ascending order -- limit 0;
* ``dist_gap``: the widest relative gap between a returned distance and
  the float64 distance of the returned id under the deployment's
  metric -- the limit is the deployment's ``dist_gap_limit``, set
  between the program's readings and the control's (PERF.md);
* ``recall_at_10``: mean recall@10 of the returned ids against the
  exact top-10 -- at least the deployment's ``recall_at_10_min``, the
  operating point its source states.
"""
from __future__ import annotations

import numpy as np

from bench.spec import METRICS


def _check(metric: str) -> None:
    if metric not in METRICS:
        raise ValueError(f"metric {metric!r} is not one of {METRICS}")


def _unit(a):
    """Rows of ``a`` scaled to unit L2 norm in float32 (a zero row
    stays zero)."""
    import jax.numpy as jnp
    a = a.astype(jnp.float32)
    n = jnp.sqrt(jnp.sum(a * a, axis=-1, keepdims=True))
    return a / jnp.maximum(n, 1e-30)


def exact_topk(x: np.ndarray, q: np.ndarray, k: int, *, metric: str,
               block: int = 1 << 16, qblock: int = 4096,
               operand_dtype: str = "float32") -> np.ndarray:
    """Exact top-k ids of each query over ``x`` under ``metric``.
    HIGHEST precision keeps a float32 matmul in float32 on a TPU (its
    default rounds operands to bfloat16); ``operand_dtype="bfloat16"``
    rounds the operands on purpose and accumulates in float32."""
    import jax
    import jax.numpy as jnp

    _check(metric)
    dt = jnp.dtype(operand_dtype)
    prec = jax.lax.Precision.HIGHEST if dt == jnp.float32 else None

    @jax.jit
    def step(best_d, best_i, xall, b, qd):
        xb = jax.lax.dynamic_index_in_dim(xall, b, keepdims=False)
        base = b * xb.shape[0]
        n_valid = n - base
        if metric == "cosine":
            xb, qd = _unit(xb), _unit(qd)
        xb = xb.astype(dt)
        dot = jnp.dot(qd.astype(dt), xb.T, precision=prec,
                      preferred_element_type=jnp.float32)
        if metric == "l2":
            # |q|^2 is the same for every row of a query: left out
            xn = jnp.sum(xb.astype(jnp.float32) ** 2, axis=1)
            d = xn[None, :] - 2.0 * dot
        else:
            # 1 - q.x ranks as -q.x
            d = -dot
        col = jnp.arange(xb.shape[0], dtype=jnp.int32)
        d = jnp.where(col[None, :] < n_valid, d, jnp.inf)
        cd = jnp.concatenate([best_d, d], axis=1)
        ci = jnp.concatenate(
            [best_i, jnp.broadcast_to(base + col, d.shape)], axis=1)
        neg, pos = jax.lax.top_k(-cd, k)
        return -neg, jnp.take_along_axis(ci, pos, axis=1)

    n = len(x)
    block = min(block, n)
    n_blocks = -(-n // block)
    xpad = np.zeros((n_blocks * block, x.shape[1]), np.float32)
    xpad[:n] = x
    xd = jnp.asarray(xpad.reshape(n_blocks, block, -1))
    out = np.empty((len(q), k), np.int64)
    qblock = min(qblock, len(q))
    for qs in range(0, len(q), qblock):
        qb = np.zeros((qblock, q.shape[1]), np.float32)
        qb[:len(q) - qs] = q[qs:qs + qblock]
        qd = jnp.asarray(qb)
        best_d = jnp.full((qblock, k), jnp.inf, jnp.float32)
        best_i = jnp.full((qblock, k), -1, jnp.int32)
        for b in range(n_blocks):
            best_d, best_i = step(best_d, best_i, xd, jnp.int32(b), qd)
        out[qs:qs + qblock] = np.asarray(best_i)[:len(q) - qs]
    return out


def dists_f64(x: np.ndarray, q: np.ndarray, ids: np.ndarray, *,
              metric: str, block: int = 4096) -> np.ndarray:
    """[n, k] float64 distance under ``metric`` of each query to each
    of its ids (ids must be in range)."""
    _check(metric)
    out = np.empty(ids.shape, np.float64)
    for s in range(0, len(q), block):
        xs = x[ids[s:s + block]].astype(np.float64)
        qs = q[s:s + block].astype(np.float64)
        if metric == "l2":
            diff = xs - qs[:, None, :]
            out[s:s + block] = np.einsum("nkd,nkd->nk", diff, diff)
            continue
        dot = np.einsum("nkd,nd->nk", xs, qs)
        if metric == "cosine":
            dot /= (np.linalg.norm(xs, axis=-1)
                    * np.linalg.norm(qs, axis=-1)[:, None])
        out[s:s + block] = 1.0 - dot
    return out


def bf16_dists(x: np.ndarray, q: np.ndarray, ids: np.ndarray, *,
               metric: str, block: int = 4096) -> np.ndarray:
    """The control's distances under ``metric``: bfloat16-rounded
    operands (under ``cosine`` normalised in float32 first), summed in
    float32."""
    import jax.numpy as jnp
    _check(metric)
    out = np.empty(ids.shape, np.float32)
    for s in range(0, len(q), block):
        xs = jnp.asarray(x[ids[s:s + block]])
        qs = jnp.asarray(q[s:s + block])
        if metric == "cosine":
            xs, qs = _unit(xs), _unit(qs)
        xs = xs.astype(jnp.bfloat16).astype(jnp.float32)
        qs = qs.astype(jnp.bfloat16).astype(jnp.float32)[:, None]
        if metric == "l2":
            diff = xs - qs
            out[s:s + block] = np.asarray(jnp.sum(diff * diff, axis=-1))
        else:
            out[s:s + block] = np.asarray(1.0 - jnp.sum(xs * qs, axis=-1))
    return out


def recall_at(ids: np.ndarray, gt: np.ndarray, k: int = 10) -> float:
    """Mean recall@k of ``ids`` [n, >=k] against ``gt`` [n, >=k]."""
    ids, gt = np.asarray(ids)[:, :k], np.asarray(gt)[:, :k]
    return float((ids[:, :, None] == gt[:, None, :]).any(-1).mean())


def bad_rows(ids: np.ndarray, dists: np.ndarray, n: int, k: int
             ) -> np.ndarray:
    """[rows] bool: fewer than k ids, an id out of [0, n), a repeated
    id, a non-finite distance, or distances out of ascending order."""
    ids = np.asarray(ids)
    dists = np.asarray(dists, np.float64)
    bad = (ids.shape[1] < k) | (ids < 0).any(1) | (ids >= n).any(1)
    bad |= ~np.isfinite(dists).all(1)
    srt = np.sort(ids, axis=1)
    bad |= (srt[:, 1:] == srt[:, :-1]).any(1)
    bad |= (np.diff(dists, axis=1) < 0).any(1)
    return bad


def dist_gap(x: np.ndarray, q: np.ndarray, ids: np.ndarray,
             dists: np.ndarray, *, metric: str) -> float:
    """Widest relative gap ``|d - ref| / max(|ref|, floor)`` between a
    returned distance and the float64 distance of its id, with ``floor``
    a millionth of the median ``|ref|`` (``ip``'s distances may be
    negative or near zero); rows must be in range (see ``bad_rows``)."""
    if not len(ids):
        return 0.0
    ref = dists_f64(x, q, ids, metric=metric)
    mag = np.abs(ref)
    floor = 1e-6 * max(float(np.median(mag)), 1e-30)
    return float(np.max(np.abs(np.asarray(dists, np.float64) - ref)
                        / np.maximum(mag, floor)))


def judge(x: np.ndarray, q: np.ndarray, ids: np.ndarray,
          dists: np.ndarray, gt: np.ndarray, *, unanswered: int,
          limits: dict, metric: str, k: int = 10) -> dict:
    """The compared numbers of one run, each beside its limit, and the
    verdict. ``ids``/``dists`` [n, k] are the answers, row i for query
    ``q[i]``; ``gt`` [n, >=10] the exact ids under ``metric``."""
    bad = bad_rows(ids, dists, len(x), k)
    ok = ~bad
    checks = {
        "unanswered": {"value": int(unanswered), "limit": 0},
        "bad_rows": {"value": int(bad.sum()), "limit": 0},
        "dist_gap": {"value": dist_gap(x, q[ok], ids[ok], dists[ok],
                                       metric=metric),
                     "limit": float(limits["dist_gap_limit"])},
        "recall_at_10": {"value": recall_at(ids, gt, 10)
                         if len(ids) else 0.0,
                         "limit": float(limits["recall_at_10_min"])},
    }
    correct = (checks["unanswered"]["value"] == 0
               and checks["bad_rows"]["value"] == 0
               and len(ids) > 0
               and checks["dist_gap"]["value"]
               <= checks["dist_gap"]["limit"]
               and checks["recall_at_10"]["value"]
               >= checks["recall_at_10"]["limit"])
    return {"correct": bool(correct), "checks": checks}
