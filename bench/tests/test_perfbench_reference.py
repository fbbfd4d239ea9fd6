"""The plain reference under each distance metric, on the CPU at small
sizes: the exact top-k against a float64 brute force, the gap that
reads nothing on float64 distances and separates the bfloat16 control,
and the inputs of the existing deployments pinned to what they were
before the deployment's file named its data generator and metric."""
from __future__ import annotations

import hashlib

import numpy as np
import pytest

from _tiny import ROOT  # noqa: F401  (puts the repo on sys.path)
from bench import deploy, reference, spec

METRICS = spec.METRICS


def _data(n=3000, n_q=64, dim=32, seed=11):
    """Signed Gaussian rows and queries whose norms span a factor of
    about 50, so that l2, cosine and ip rank them differently."""
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, dim)) * np.exp(rng.uniform(-2, 2, (n, 1)))
    q = rng.standard_normal((n_q, dim)) * np.exp(rng.uniform(-1, 1,
                                                             (n_q, 1)))
    return x.astype(np.float32), q.astype(np.float32)


def _brute_f64(x, q, metric):
    x64, q64 = x.astype(np.float64), q.astype(np.float64)
    if metric == "l2":
        return ((q64[:, None, :] - x64[None]) ** 2).sum(-1)
    dot = q64 @ x64.T
    if metric == "cosine":
        dot /= np.linalg.norm(q64, axis=1)[:, None] \
            * np.linalg.norm(x64, axis=1)[None]
    return 1.0 - dot


@pytest.mark.parametrize("metric", METRICS)
def test_exact_topk_matches_f64_brute_force(metric):
    x, q = _data()
    d = _brute_f64(x, q, metric)
    want = np.argsort(d, axis=1, kind="stable")[:, :10]
    got = reference.exact_topk(x, q, 10, metric=metric, block=1024,
                               qblock=48)
    assert np.array_equal(got, want)
    np.testing.assert_allclose(
        reference.dists_f64(x, q, got, metric=metric),
        np.take_along_axis(d, got, axis=1), rtol=1e-12, atol=1e-12)


def test_metrics_rank_differently():
    x, q = _data()
    top = {m: reference.exact_topk(x, q, 10, metric=m) for m in METRICS}
    for a, b in (("l2", "cosine"), ("l2", "ip"), ("cosine", "ip")):
        same = [set(r) == set(s) for r, s in zip(top[a], top[b])]
        assert np.mean(same) < 0.5, (a, b)


def _ids(x, q, metric):
    """Each query's exact top-10 beside 10 rows drawn at random: the
    distances then span near and far (and, under ip, both signs)."""
    top = reference.exact_topk(x, q, 10, metric=metric)
    far = np.random.default_rng(3).integers(0, len(x), top.shape)
    return np.concatenate([top, far], axis=1)


@pytest.mark.parametrize("metric", METRICS)
def test_dist_gap_is_zero_on_f64_distances(metric):
    x, q = _data()
    ids = _ids(x, q, metric)
    ref = reference.dists_f64(x, q, ids, metric=metric)
    if metric == "ip":
        assert (ref < 0).any() and (ref > 0).any()
    assert reference.dist_gap(x, q, ids, ref, metric=metric) == 0.0


@pytest.mark.parametrize("metric", METRICS)
def test_dist_gap_separates_bf16_control(metric):
    x, q = _data()
    ids = _ids(x, q, metric)
    ctl = reference.bf16_dists(x, q, ids, metric=metric)
    assert reference.dist_gap(x, q, ids, ctl, metric=metric) > 1e-4


def test_dist_gap_formula_for_signed_distances():
    """|d - ref| / max(|ref|, 1e-6 x median |ref|): a negative distance
    is measured against its magnitude, a distance of 0 against the
    floor."""
    x = np.array([[1.0, 0.0], [2.0, 0.0], [0.0, 1.0]], np.float32)
    q = np.array([[1.0, 0.0]], np.float32)
    ids = np.array([[0, 1, 2]])
    ref = reference.dists_f64(x, q, ids, metric="ip")
    assert ref.tolist() == [[0.0, -1.0, 1.0]]
    d = ref + np.array([[1e-9, -1e-4, 2e-4]])
    # floor = 1e-6 x median(0, 1, 1) = 1e-6
    assert reference.dist_gap(x, q, ids, d, metric="ip") \
        == pytest.approx(1e-3)
    d = ref + np.array([[0.0, -0.01, 0.02]])
    assert reference.dist_gap(x, q, ids, d, metric="ip") \
        == pytest.approx(0.02)


def test_unknown_metric_is_an_error():
    x, q = _data(n=64, n_q=4)
    ids = np.zeros((4, 10), np.int64)
    with pytest.raises(ValueError):
        reference.exact_topk(x, q, 10, metric="dot")
    with pytest.raises(ValueError):
        reference.dists_f64(x, q, ids, metric="angular")
    with pytest.raises(ValueError):
        reference.bf16_dists(x, q, ids, metric="L2")


def _h(a: np.ndarray) -> str:
    a = np.ascontiguousarray(a)
    return hashlib.sha256(a.dtype.str.encode() + str(a.shape).encode()
                          + a.tobytes()).hexdigest()[:16]


# the data, the query pool's rows (three of its blocks), the warm-up's
# queries and the exact top-10 ids at 2,000 points for seed 2**31 + 7,
# as the harness made them before a deployment's file named its data
# generator and metric; both deployments share these data parameters
PINNED = {"x": "a32f677f657eb956", "pool": "8807d11da912291e",
          "warm": "30c10535d10e8558", "top10": "913155d33a09913a"}


@pytest.mark.parametrize("config", ["sift250k-pca", "sift250k-cascade"])
def test_existing_inputs_are_pinned(config):
    seed = 2**31 + 7
    cfg = {**spec.config(config), "n_points": 2000}
    x = deploy.make_data(cfg, seed)
    q = deploy.QueryPool(cfg, x, seed, 1)[np.arange(0, 9000, 7)]
    warm = deploy.make_queries(cfg, x, 100, seed, 2)
    top = reference.exact_topk(x, q, 10, metric=cfg["metric"])
    assert {"x": _h(x), "pool": _h(q), "warm": _h(warm),
            "top10": _h(top.astype(np.int64))} == PINNED
