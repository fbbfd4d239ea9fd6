"""Ahead-of-time compiles for a described TPU v5e: the served kernels at
real widths and the scheduler's tick program, lowered and compiled by
the TPU compiler installed here with no chip attached. What Mosaic or
XLA:TPU refuses here (unaligned blocks, unsupported reductions, VMEM
overflow) would fail on the chip. Every test asserts that the compiled
program holds the Pallas kernel (``tpu_custom_call``): no jnp oracle
and no interpret-mode body was traced.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library at a time, and a test worker
that loads it keeps it until it exits."""
from __future__ import annotations

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.sift1m_phnsw import CONFIG
from repro.core import search_jax as sj
from repro.kernels import ops


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("no TPU compiler (libtpu) in this installation")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_path(monkeypatch):
    """Steer the kernel wrappers to the compiled Pallas path (off the
    TPU they would trace the jnp oracles), with no program traced under
    the other path left in the caches on either side."""
    monkeypatch.setattr(ops, "kernel_path", lambda: "compiled")
    jax.clear_caches()
    yield
    jax.clear_caches()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _compile_text(fn, *args, **kw) -> str:
    return fn.lower(*args, **kw).compile().as_text()


B, M0, DL, K0 = 64, 32, 15, 16       # service batch, layer-0 degree,
#                                      PCA width, layer-0 k (sift1m)


def test_fused_expand_compiles(one_chip, compiled_path):
    s = lambda *a: _sds(*a, one_chip)
    txt = _compile_text(ops.fused_expand, s((B, M0, DL), "float32"),
                        s((B, DL), "float32"), s((B, M0), "bool"),
                        s((B,), "float32"), k=K0)
    assert "tpu_custom_call" in txt


def test_ksort_l_compiles(one_chip, compiled_path):
    # the cross-shard merge width: 4 shards x an ef0-wide list each
    txt = _compile_text(ops.ksort_l, _sds((B, 4 * 10), "float32",
                                          one_chip), k=10)
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("D", [128, 960])
def test_dist_h_compiles(one_chip, compiled_path, D):
    txt = _compile_text(ops.dist_h, _sds((B, K0, D), "float32", one_chip),
                        _sds((B, D), "float32", one_chip))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("na,nb,k", [(26, 16, 26), (10, 16, 10),
                                     (16, 16, 16)])
def test_merge_topk_sorted_compiles(one_chip, compiled_path, na, nb, k):
    """The three per-step merges of the layer-0 body (C frontier, F
    results, C_pca heap) at the sift1m widths."""
    s = lambda *a: _sds(*a, one_chip)
    txt = _compile_text(ops.merge_topk_sorted, s((B, na), "float32"),
                        s((B, na), "int32"), s((B, nb), "float32"),
                        s((B, nb), "int32"), k=k)
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("k", [K0, M0])
def test_pq_adc_expand_compiles(one_chip, compiled_path, k):
    """S=16 sub-quantizers at M0=32: the pq filter (k=16) and the
    deferred cascade, which keeps every layer-0 neighbor (k=M0)."""
    s = lambda *a: _sds(*a, one_chip)
    txt = _compile_text(ops.pq_adc_expand, s((B, M0, 16), "uint8"),
                        s((B, 16, 256), "float32"), s((B, M0), "bool"),
                        s((B,), "float32"), k=k)
    assert "tpu_custom_call" in txt


def test_pick_block_b_rows():
    """A 2-D block's row count is 8 (Mosaic's sublane tile) or the
    whole batch when it has fewer rows."""
    assert [ops._pick_block_b(b) for b in (1, 5, 8, 9, 64, 2048)] \
        == [1, 5, 8, 8, 8, 8]


def test_scheduler_tick_compiles(one_chip, compiled_path):
    """The fused admit+step tick program of the slot scheduler over a
    PCA index of 64k vectors (the sift1m layer shapes: six layers,
    M=16/M0=32, d_low=15, tombstone bitmap present as in MutableIndex),
    at the full 64-slot width."""
    n, S = 1 << 16, B
    s = lambda *a: _sds(*a, one_chip)
    cfg = CONFIG
    layers = [sj.PackedLayer(adj=s((n, cfg.degree(l)), "int32"),
                             packed_low=s((n, cfg.degree(l), DL),
                                          "float32"))
              for l in range(cfg.n_layers)]
    db = sj.PackedDB(layers=layers, low=s((n, DL), "float32"),
                     high=s((n, cfg.dim), "float32"),
                     entry=s((), "int32"), cfg=cfg,
                     deleted=s((n // 32,), "int32"), filter_kind="pca")
    state = sj.make_slot_state(db, S, np.zeros((1, DL), np.float32),
                               ef=cfg.ef0)
    state = jax.tree.map(lambda a: s(a.shape, a.dtype), state)
    txt = _compile_text(
        sj._slot_admit_step_jit, db, state, s((S, cfg.dim), "float32"),
        s((S, DL), "float32"), s((S,), "int32"), s((S,), "int32"),
        s((S,), "int32"), width=S, quantum=32, expand_width=1)
    assert txt.count("tpu_custom_call") > 0
