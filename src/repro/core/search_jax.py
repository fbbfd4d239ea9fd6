"""Batched fixed-shape pHNSW search in JAX — the TPU-native adaptation.

The ASIC processes one query with data-dependent control flow; a TPU
wants a BATCH of queries with fixed shapes. This module runs B queries
simultaneously through Algorithm 1 with:

  * packed layout (3) as a device array ``packed_low[N, M, dl]`` — one
    row gather per expansion fetches indices + all neighbor low-dim
    vectors (the regular-access insight, HBM edition), storable in
    bfloat16 (``PHNSWConfig.low_dtype``) to halve the dominant stream;
  * the FUSED expand kernel (``ops.fused_expand``): Dist.L, the
    adjacency/active mask, the C_pca threshold compare and kSort.L in a
    single VMEM residency — one kernel per expansion step instead of a
    Dist.L -> HBM -> kSort.L round-trip;
  * sorted frontiers: C (candidates), F (finals) and C_pca are kept
    ascending-sorted loop invariants, so the pop is slot 0 and every
    per-step merge is an O(ef+k) sorted merge (``ops.merge_topk_sorted``)
    instead of a concat + O((CAP+k)^2) comparison-matrix re-sort;
  * fixed-capacity candidate/final buffers with masked updates inside
    ``lax.while_loop`` (no data-dependent shapes anywhere), and the
    ASIC's per-query visited BITMAP (one bit per node, packed into
    int32 words — membership is a single word gather per candidate);
  * per-query ``done`` masks carried as loop state (termination is
    monotone, so freezing is latched), per-query step telemetry, and a
    global early exit once every query in the batch has frozen — the
    convoy-mitigation story (DESIGN.md).

Formulation note (DESIGN.md): every small sort/merge here is a
comparison-matrix + one-hot contraction, NOT lax.sort/gather — XLA
lowers variadic sorts and gathers to scalar loops on CPU and the widths
involved (M, k, CAP) are tiny, so the O(n^2) vector form wins on every
backend this repo targets.
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs.base import PHNSWConfig
from repro.constants import INF as _INF, VALID_MAX
from repro.core.filters import prepare_cache_size
from repro.core.graph import HNSWGraph
from repro.kernels import ops

INF = jnp.float32(_INF)


@dataclass
class PackedLayer:
    adj: jax.Array          # [N, M] int32, -1 padded
    packed_low: jax.Array   # [N, M, dl] neighbor low-dim data, inline


@dataclass
class PackedDB:
    """Device-resident database in the paper's layout (3).

    ``entry`` is a pytree DATA field (a scalar, traced under jit), not
    metadata: the mutable-index subsystem re-points the entry when a new
    top-level node is inserted, and a metadata entry would key the jit
    cache — every entry change would recompile the search program.

    ``deleted`` is an optional word-packed tombstone bitmap,
    ``[ceil(N/32)] int32`` (bit i of word i>>5 = node i is deleted).
    ``None`` (the default, a structurally static distinction) means "no
    tombstones ever": the engine then compiles the plain accept path.
    When present, deleted nodes are TRAVERSED (they stay in the
    candidate frontier, their neighbors are expanded) but never RETURNED
    (they are excluded from the result list F on the output layer).

    ``filter_kind`` is METADATA (static): which filter stage the
    payload in ``low`` / ``packed_low`` belongs to — "pca" (dense
    low-dim rows), "pq" (uint8 ADC codes), "cascade" (uint8 ADC codes
    inline + a PCA side-car) or "none" (zero-width bypass payload).
    Each kind compiles a different expand pipeline, so it is structural
    by design (core/filters.py owns the payload contract).

    ``low2`` is the cascade's SIDE-CAR payload: f32 PCA rows
    ``[N, d_low]``, stored OFF the layout-(3) hot stream (never inlined
    per neighbor) and gathered once per query at the promote stage.
    ``None`` (every non-cascade kind) is structurally static, like
    ``deleted``."""
    layers: List[PackedLayer]
    low: jax.Array          # [N, P] filter payload rows (P may be 0)
    high: jax.Array         # [N, D]
    entry: int
    cfg: PHNSWConfig
    deleted: Optional[jax.Array] = None   # [ceil(N/32)] int32 or None
    low2: Optional[jax.Array] = None      # [N, dl] promote side-car
    filter_kind: str = "pca"

    @property
    def bytes_layout3(self) -> int:
        """Stored bytes under the paper's layout (3): per RESIDENT node
        per layer, the neighbor list with inline low-dim vectors
        (non-padded entries), plus the high-dim table. (The device arrays
        keep full-N rows for gather regularity; the accounting reflects
        what a packed store would hold.)"""
        dl = self.low.shape[1]
        low_bytes = jnp.dtype(self.low.dtype).itemsize
        extra = 0
        for l in self.layers:
            nnz = int((l.adj >= 0).sum())
            extra += nnz * (4 + dl * low_bytes)
        return extra + int(self.high.size) * 4

    @property
    def bytes_sidecar(self) -> int:
        """Stored bytes of the cascade's promote side-car (0 without
        one) — NOT part of the layout-(3) inline stream the traversal
        bursts; reported separately by the byte accounting."""
        if self.low2 is None:
            return 0
        return int(self.low2.size) * jnp.dtype(self.low2.dtype).itemsize

    @property
    def bytes_layout4(self) -> int:
        idx = sum(int((l.adj >= 0).sum()) * 4 for l in self.layers)
        low_bytes = jnp.dtype(self.low.dtype).itemsize
        return idx + int(self.low.size) * low_bytes \
            + int(self.high.size) * 4


# pytree registration so whole searches can be jit'd / shard_map'd
jax.tree_util.register_dataclass(
    PackedLayer, data_fields=["adj", "packed_low"], meta_fields=[])
jax.tree_util.register_dataclass(
    PackedDB, data_fields=["layers", "low", "high", "entry", "deleted",
                           "low2"],
    meta_fields=["cfg", "filter_kind"])


def _tombstone_bit(deleted, ids):
    """Gather the tombstone bit for an int32 id array (any shape).
    Negative ids (padding) read word 0 harmlessly; callers mask them."""
    safe = jnp.maximum(ids, 0)
    return (jnp.take(deleted, safe // 32) >> (safe % 32)) & 1 != 0


def pack_bitmap(flags: np.ndarray) -> np.ndarray:
    """bool [n] -> int32 words [ceil(n/32)] in the ``_tombstone_bit``
    layout (bit i of word i >> 5 = flags[i]); the tail word is
    zero-padded. The ONE definition of the on-device tombstone word
    layout — the mutable index and the sharded builder both pack
    through here."""
    nw = -(-len(flags) // 32)
    words = np.zeros(nw, np.uint32)
    ids = np.nonzero(flags)[0].astype(np.uint32)
    np.bitwise_or.at(words, ids // 32, np.uint32(1) << (ids % 32))
    return words.view(np.int32)


def build_packed(g: HNSWGraph, x_low: Optional[np.ndarray] = None,
                 *, filt=None, low_dtype: Optional[str] = None,
                 drop_empty_layers: bool = True) -> PackedDB:
    """``x_low`` is the filter payload ([N, P] rows — dense low-dim
    vectors for the default PCA filter); passing ``filt`` (a
    ``core.filters.FilterSpec``) instead encodes the payload from the
    filter and stamps its kind onto the db ("pca" assumed otherwise).
    ``low_dtype`` overrides ``g.cfg.low_dtype`` (layout-(3) storage
    dtype of the inline PCA vectors; distances still run in f32; PQ
    codes always store uint8). ``drop_empty_layers`` skips all-padding
    top layers (the level assignment rarely reaches cfg.n_layers at
    small N) so the search never runs a while_loop over an empty graph
    layer; pass False when layer counts must stay uniform (e.g.
    stacking shards)."""
    h = pack_host(g, x_low, filt=filt, low_dtype=low_dtype,
                  drop_empty_layers=drop_empty_layers)
    layers = [PackedLayer(adj=jnp.asarray(a), packed_low=jnp.asarray(p))
              for a, p in zip(h.adj, h.packed_low)]
    return PackedDB(layers=layers, low=jnp.asarray(h.low),
                    high=jnp.asarray(g.x), entry=g.entry, cfg=g.cfg,
                    low2=None if h.low2 is None else jnp.asarray(h.low2),
                    filter_kind=h.filter_kind)


@dataclass
class HostPacked:
    """``build_packed``'s arrays before upload (numpy, in device dtype):
    the sharded builder stacks these and places each shard on its own
    device without staging whole shards on the default one."""
    adj: List[np.ndarray]          # per layer [N, M_l] int32
    packed_low: List[np.ndarray]   # per layer [N, M_l, P]
    low: np.ndarray                # [N, P]
    low2: Optional[np.ndarray]     # [N, d_low] cascade side-car or None
    filter_kind: str


def pack_host(g: HNSWGraph, x_low: Optional[np.ndarray] = None, *,
              filt=None, low_dtype: Optional[str] = None,
              drop_empty_layers: bool = True) -> HostPacked:
    """The host half of ``build_packed`` (same arguments)."""
    fkind = filt.kind if filt is not None else "pca"
    if x_low is None:
        if filt is None:
            raise ValueError("build_packed needs x_low or filt")
        x_low = filt.encode(g.x)
    dt = jnp.dtype(low_dtype or g.cfg.low_dtype) if fkind == "pca" \
        else jnp.dtype(x_low.dtype)
    adjs = list(g.layers)
    if drop_empty_layers:
        while len(adjs) > 1 and not (adjs[-1] >= 0).any():
            adjs.pop()
    packed_low = []
    for adj in adjs:
        safe = np.where(adj >= 0, adj, 0)
        packed = x_low[safe]                       # [N, M, P]
        packed[adj < 0] = 0
        packed_low.append(packed.astype(dt, copy=False))
    low2 = None
    if filt is not None and hasattr(filt, "encode_mid"):
        # the cascade's promote side-car: PCA rows off the hot stream
        low2 = filt.encode_mid(g.x)
    return HostPacked(adj=[np.asarray(a) for a in adjs],
                      packed_low=packed_low,
                      low=np.asarray(x_low).astype(dt, copy=False),
                      low2=low2, filter_kind=fkind)


def _rank_sort_with_payload(d, p):
    """Stable ascending sort of each row of d (ties -> lower slot), the
    int payload p carried along. Same (dist, slot) order as
    ref.ksort_l_ref — merge_topk_sorted's determinism depends on the
    tie-break matching — but applies the payload through the rank
    one-hot instead of ksort_l + take_along_axis: n is small (W*k) and
    XLA CPU lowers lax.sort/gather to scalar loops."""
    B, n = d.shape
    ii = jnp.arange(n)
    idx_gt = (ii[:, None] > ii[None, :])[None]
    cmp = (d[:, :, None] > d[:, None, :]) \
        | ((d[:, :, None] == d[:, None, :]) & idx_gt)
    rank = cmp.sum(-1).astype(jnp.int32)
    hot = rank[:, :, None] == ii[None, None, :]          # [B, n, n]
    sd = jnp.sum(jnp.where(hot, d[:, :, None], 0.0), axis=1)
    sp = jnp.sum(jnp.where(hot, p[:, :, None], 0), axis=1).astype(p.dtype)
    return sd, sp


def _cascade_lut(qprep, S: int):
    """ADC tables out of the cascade's flat per-query prep:
    [B, S*256 + d_low] -> [B, S, 256]. ``S`` is static — the inline
    payload width (``db.low.shape[-1]``), so the slice never depends on
    traced values."""
    return qprep[:, :S * 256].reshape(qprep.shape[0], S, 256)


def _cascade_qpca(qprep, S: int):
    """The PCA-projected query out of the cascade's flat prep:
    [B, S*256 + d_low] -> [B, d_low] (the promote-stage operand)."""
    return qprep[:, S * 256:]


def _layer_init(db: PackedDB, start_d, start_i, *, ef: int, k: int,
                CAP: int, filter_deleted: bool, bitmap: bool = True):
    """The fixed-capacity SORTED layer state seeded from a start set:
    (C_d, C_i, F_d, F_i, V, Cp). Shared by ``search_layer_batched``
    (fresh per layer) and the slotted admission path (fresh per
    admitted query, scattered into a live ``SlotState``)."""
    B = start_d.shape[0]
    N = db.high.shape[0]
    pad = CAP - start_d.shape[1]
    C_d = jnp.pad(start_d, ((0, 0), (0, pad)), constant_values=INF)
    C_i = jnp.pad(start_i, ((0, 0), (0, pad)), constant_values=-1)
    if filter_deleted:
        # seed F with the LIVE subset of the start set (the routing
        # layers above may hand us tombstoned entry points: legal to
        # traverse from, illegal to return)
        tomb0 = _tombstone_bit(db.deleted, start_i) | (start_i < 0)
        s_d, s_i = _rank_sort_with_payload(
            jnp.where(tomb0, INF, start_d),
            jnp.where(tomb0, -1, start_i))
        epad = max(ef - s_d.shape[1], 0)
        F_d = jnp.pad(s_d, ((0, 0), (0, epad)),
                      constant_values=INF)[:, :ef]
        F_i = jnp.pad(s_i, ((0, 0), (0, epad)),
                      constant_values=-1)[:, :ef]
    else:
        F_d, F_i = C_d[:, :ef], C_i[:, :ef]    # best ef of the start set
    # visited bitmap, the ASIC's SPM bitmap verbatim: one bit per node,
    # packed into int32 words; membership = one word gather per
    # candidate, insert = scatter-add of (disjoint) bit masks
    nw = -(-N // 32) if bitmap else 1     # no bitmap: a dummy word
    V = jnp.zeros((B, nw), jnp.int32)
    if not bitmap:
        return C_d, C_i, F_d, F_i, V, jnp.full((B, k), INF)
    sw, sb = start_i // 32, start_i % 32
    V = jax.vmap(lambda v, w, m: v.at[w].add(m))(
        V, sw, jnp.where(start_i >= 0, (1 << sb).astype(jnp.int32), 0))
    # C_pca threshold heap (k-bounded filter dists of accepted
    # candidates, ascending; Cp[-1] is the filter threshold f_pca).
    # The identity filter has no threshold stage — Cp stays a constant
    # INF row and its merge is elided from the compiled program.
    Cp = jnp.full((B, k), INF)
    return C_d, C_i, F_d, F_i, V, Cp


def _layer_body(db: PackedDB, layer: int, q_high, qprep, *, ef: int,
                k: int, W: int, steps, filter_deleted: bool,
                deferred: bool, ef_eff=None, budget=None,
                bitmap: bool = True):
    """Build the ONE-expansion-iteration body over the layer state
    tuple ``(t, C_d, C_i, F_d, F_i, V, Cp, done, nsteps, dhe)``.

    ``search_layer_batched`` drives it inside a ``lax.while_loop`` with
    a static per-layer ``steps`` budget; the slotted stepper
    (``_slot_step_jit``) drives the SAME body with two per-slot DATA
    generalizations, both exactly the static program when absent:

    * ``ef_eff`` [B] int32 — the per-slot effective ef: the acceptance
      /termination bound reads ``F_d[i, ef_eff[i]-1]`` instead of
      ``F_d[i, -1]``, so a slot converges once its top-``ef_eff``
      results are stable even though the compiled buffers are ``ef``
      wide (the adaptive-ef and mixed-k hook);
    * ``budget`` [B] int32 — the per-slot expansion-step budget
      replacing the static ``steps`` limit (the adaptive step-budget
      hook: a stalled slot freezes without latching ``done`` and
      resumes when the scheduler raises its budget).

    ``bitmap=False`` (identity filter, no tombstones, static ef and
    budget only) drops the visited bitmap: a candidate counts as seen
    iff it is in C or F. The traversal is the same: any other visited
    node has d >= F.max, which only shrinks, so it is rejected again.
    Only the Dist.H count grows, by those re-evaluations."""
    assert bitmap or (db.filter_kind == "none" and not filter_deleted
                      and ef_eff is None and budget is None)
    B = q_high.shape[0]
    lay = db.layers[layer]
    M = lay.adj.shape[1]
    fkind = db.filter_kind
    if fkind == "none":
        kk = W * M          # filter bypass: every neighbor is a candidate
        deferred = False    # filter space == high-dim space
    else:
        kk = W * k                               # survivors per iteration

    def body(state):
        t, C_d, C_i, F_d, F_i, V, Cp, done, nsteps, dhe = state
        C_seen = C_i                        # before this step's pop
        # the acceptance/termination bound: F.max over the slot's
        # effective result width (the full compiled width when no
        # per-slot ef is active — bit-identical to the original)
        if ef_eff is None:
            bnd = F_d[:, -1:]
        else:
            bnd = jnp.take_along_axis(
                F_d, jnp.maximum(ef_eff, 1)[:, None] - 1, axis=1)
        lim = steps if budget is None else budget[:, None]
        # -- pop the W nearest candidates: slots 0..W-1 of sorted C --
        d_w, c_w = C_d[:, :W], C_i[:, :W]
        # termination is monotone (F.max only shrinks, the popped min
        # only grows), so the freeze is latched per query; frozen
        # queries keep popping into masked work, which is harmless.
        # An exhausted frontier (slot 0 is the -1/INF pad) also
        # latches: nothing left to expand can ever improve F — this is
        # what the host reference's "while C" does, and without it a
        # query on a sparse/empty layer spins through the whole step
        # budget doing masked work (the construction probe publishes
        # not-yet-populated top layers, where that spin dominates)
        done = done | (C_d[:, 0] > bnd[:, 0]) \
            | (C_i[:, 0] < 0)                           # lines 7-8
        # per-slot expansion gate: a popped candidate past F.max is
        # dead forever, so dropping it unexpanded is exact; the budget
        # term keeps total expansions <= steps even when W ∤ steps
        exp = (d_w <= bnd) & ~done[:, None] \
            & (nsteps[:, None] + jnp.arange(W)[None, :] < lim)
        sh_d = jnp.concatenate([C_d[:, W:], jnp.full((B, W), INF)], 1)
        sh_i = jnp.concatenate([C_i[:, W:],
                                jnp.full((B, W), -1, jnp.int32)], 1)
        if budget is None:
            # static budget == the loop's iteration bound: every body
            # application is a real pop (the original program, verbatim)
            C_d, C_i = sh_d, sh_i
        else:
            # slotted: a budget-frozen (or done) slot must NOT pop — it
            # keeps its frontier intact and resumes exactly where it
            # froze when the scheduler raises its budget
            alive = (~done & (nsteps < budget))[:, None]
            C_d = jnp.where(alive, sh_d, C_d)
            C_i = jnp.where(alive, sh_i, C_i)
        # gated-off slots gather row 0 (cheap, discarded via the mask)
        c_safe = jnp.where(exp, jnp.maximum(c_w, 0), 0)
        # -- step 2: W row gathers = paper layout (3) bursts --
        nb_i = jnp.take(lay.adj, c_safe.reshape(-1), axis=0) \
            .reshape(B, -1)                             # [B, W*M]
        nb_mask = (nb_i >= 0) & jnp.repeat(exp, M, axis=1)
        if fkind == "none":
            # filter bypass: every valid neighbor is a candidate (slot
            # order = adjacency order); no payload gather, no kernel
            cand, kv = nb_i, None
            valid = nb_mask
        else:
            nb_pay = jnp.take(lay.packed_low, c_safe.reshape(-1),
                              axis=0).reshape(B, nb_i.shape[1], -1)
            # -- fused expand: filter dist (Dist.L or PQ ADC) + mask +
            #    f_pca threshold + kSort.L in one kernel --
            th = Cp[:, -1]
            if fkind == "pca":
                kv, ki = ops.fused_expand(nb_pay, qprep, nb_mask, th, kk)
            else:
                # pq and cascade both traverse on ADC codes; the
                # cascade's luts are sliced out of its flat prep row
                lut = _cascade_lut(qprep, nb_pay.shape[-1]) \
                    if fkind == "cascade" else qprep
                kv, ki = ops.pq_adc_expand(nb_pay, lut, nb_mask, th, kk)
            cand = jnp.take_along_axis(nb_i, ki, axis=1)    # [B, W*k]
            valid = (kv < VALID_MAX) & (cand >= 0)
        # -- visited check: one bit gather per candidate --
        cw, cb = jnp.maximum(cand, 0) // 32, jnp.maximum(cand, 0) % 32
        if bitmap:
            seen = (jnp.take_along_axis(V, cw, axis=1) >> cb) & 1 != 0
        else:
            seen = (cand[:, :, None] == jnp.concatenate(
                [C_seen, F_i], 1)[:, None, :]).any(-1)
        if W > 1:
            # intra-iteration dedup (the W neighbor lists may overlap;
            # keep the first occurrence); a single list holds distinct
            # ids on every path, including the bypass
            jj = jnp.arange(kk, dtype=jnp.int32)
            dup = ((cand[:, :, None] == cand[:, None, :])
                   & (jj[None, :, None] > jj[None, None, :])
                   & valid[:, None, :]).any(-1)
            seen |= dup
        valid &= ~seen
        if deferred and fkind != "none":
            # -- deferred re-rank: traverse on FILTER distances; no
            #    high-dim gather, no Dist.H inside the loop --
            dh = jnp.where(valid, kv, INF)
        else:
            # -- step 3: kk irregular high-dim fetches + Dist.H --
            xh = jnp.take(db.high, jnp.maximum(cand, 0), axis=0)
            dh = jnp.where(valid, ops.dist_h(xh, q_high), INF)  # Dist.H
            dhe = dhe + valid.sum(axis=1, dtype=jnp.int32)
        # -- mark visited: disjoint bit masks (valid slots are distinct
        #    ids, so mod-2^32 add == bitwise or) --
        if bitmap:
            V = jax.vmap(lambda v, w, m: v.at[w].add(m))(
                V, cw, jnp.where(valid, (1 << cb).astype(jnp.int32), 0))
        # -- accept: d < F.max or F not full (F starts padded with INF) --
        accept = dh < bnd
        # one stacked stable sort orders the acceptees for every
        # frontier feed; which rows exist depends on the static mode:
        #   * okF row (tombstoned masked out) only under filter_deleted
        #   * a separate kv row for the C_pca heap only when the
        #     traversal orders by Dist.H (per-step pca/pq) — in
        #     deferred mode dh IS kv, and the bypass has no C_pca
        rows_d = [jnp.where(accept, dh, INF)]
        rows_i = [jnp.where(accept, cand, -1)]
        if filter_deleted:
            # tombstoned candidates are accepted into C (traversed) but
            # masked out of the F feed (never returned)
            tomb = _tombstone_bit(db.deleted, cand)
            okF = accept & ~tomb
            rows_d.insert(0, jnp.where(okF, dh, INF))
            rows_i.insert(0, jnp.where(okF, cand, -1))
        need_kv_row = fkind != "none" and not deferred
        if need_kv_row:
            rows_d.append(jnp.where(accept, kv, INF))
            rows_i.append(jnp.zeros((B, kk), jnp.int32))
        s_d, s_i = _rank_sort_with_payload(jnp.concatenate(rows_d, 0),
                                           jnp.concatenate(rows_i, 0))
        r = B if filter_deleted else 0
        sd, si = s_d[r:r + B], s_i[r:r + B]          # C feed (dh order)
        fd_n, fi_n = (s_d[:B], s_i[:B]) if filter_deleted else (sd, si)
        # -- fold into the sorted frontiers: O(ef+k) sorted merges,
        #    each right-sized (element work, not op count, is what the
        #    CPU/TPU vector units pay for) --
        F_d, F_i = ops.merge_topk_sorted(F_d, F_i, fd_n, fi_n, ef)
        C_d, C_i = ops.merge_topk_sorted(C_d, C_i, sd, si,
                                         C_d.shape[1])
        if fkind != "none":
            # C_pca feed: the accepted candidates' filter dists — their
            # own sort row per-step, the dh row itself when deferred
            pv = s_d[r + B:] if need_kv_row else sd
            Cp, _ = ops.merge_topk_sorted(
                Cp, jnp.zeros((B, k), jnp.int32), pv,
                jnp.zeros((B, pv.shape[1]), jnp.int32), k)
        nsteps = nsteps + exp.sum(axis=1, dtype=jnp.int32)
        return (t + 1, C_d, C_i, F_d, F_i, V, Cp, done, nsteps, dhe)

    return body


def search_layer_batched(db: PackedDB, layer: int, q_high, qprep,
                         start_d, start_i, *, ef: int, k: int,
                         max_steps: Optional[int] = None,
                         expand_width: Optional[int] = None,
                         filter_deleted: bool = False,
                         deferred: bool = False, bitmap: bool = True):
    """One layer of Algorithm 1 for a batch of queries.

    ``qprep`` is the active filter's per-query data (PCA-projected
    query [B, dl] for "pca", ADC lookup tables [B, S, 256] for "pq",
    a zero-width dummy for "none" — see core/filters.py); the filter
    kind itself is static on ``db.filter_kind`` and selects the expand
    pipeline: the fused Dist.L kernel, the fused PQ ADC kernel, or the
    filter bypass (every valid neighbor goes straight to Dist.H and the
    C_pca threshold stage disappears from the compiled program).

    start_d/start_i: [B, E] entry candidates ASCENDING (high-dim dists
    normally; FILTER-space dists when ``deferred``) — the previous
    layer's output already is.

    Each loop iteration pops the W = expand_width nearest frontier
    candidates (slots 0..W-1 of the sorted C) and expands them jointly —
    exact w.r.t. the per-candidate rule, since a popped candidate with
    d > F.max can never re-qualify (F.max only shrinks). W-fold fewer
    while_loop trips; each trip's gathers/kernels widen instead.

    ``filter_deleted`` (static; requires ``db.deleted``) applies the
    tombstone semantics: deleted nodes enter the candidate frontier C
    (and the C_pca threshold heap) and are expanded like any node, but
    are excluded from the result list F — so F.max, the acceptance
    bound, is computed over LIVE nodes only and the traversal keeps
    digging until ef live results converge.

    ``deferred`` (static) traverses purely on filter distances: no
    high-dim gathers or Dist.H inside the loop — C, F and the
    acceptance bound all live in filter space, and the caller re-ranks
    the final F list in high dim once. A no-op for the identity filter
    (its filter distance IS the high-dim distance).

    Returns (F_dist [B, ef], F_idx [B, ef] ascending, steps [B] int32 =
    per-query expansion count before that query froze, dist_h [B]
    int32 = per-query Dist.H evaluations inside this layer)."""
    B = q_high.shape[0]
    M = db.layers[layer].adj.shape[1]
    W = expand_width or db.cfg.expand_width
    kk = W * M if db.filter_kind == "none" else W * k
    CAP = max(ef + kk, 8)
    steps = max_steps or db.cfg.max_steps_for_layer(layer)
    iters = -(-steps // W)                       # expansion budget / W
    if filter_deleted:
        assert db.deleted is not None, "filter_deleted needs db.deleted"

    # --- fixed-capacity SORTED state ---
    C_d, C_i, F_d, F_i, V, Cp = _layer_init(
        db, start_d, start_i, ef=ef, k=k, CAP=CAP,
        filter_deleted=filter_deleted, bitmap=bitmap)
    done = jnp.zeros((B,), bool)
    nsteps = jnp.zeros((B,), jnp.int32)
    dhe = jnp.zeros((B,), jnp.int32)
    state = (jnp.int32(0), C_d, C_i, F_d, F_i, V, Cp, done, nsteps, dhe)

    def cond(state):
        t, *_, done, _ns, _de = state
        return (t < iters) & ~done.all()

    body = _layer_body(db, layer, q_high, qprep, ef=ef, k=k, W=W,
                       steps=steps, filter_deleted=filter_deleted,
                       deferred=deferred, bitmap=bitmap)
    out = jax.lax.while_loop(cond, body, state)
    _, _, _, F_d, F_i, _, _, _, nsteps, dhe = out
    return F_d, F_i, nsteps, dhe


@functools.partial(jax.jit,
                   static_argnames=("ef", "k", "filter_deleted",
                                    "ef_upper"))
def probe_neighborhoods(db, queries, qprep, ef, k,
                        filter_deleted=True, ef_upper=None):
    """On-device neighborhood probe for a batch of to-be-inserted
    vectors: the serving traversal run at every layer with the
    construction beam (ef = ef_construction), each layer's full top-ef
    seeding the next (richer than the serial ef=1 descent). The C-phase
    device half shared by the wave builder (``core/build.py``) and the
    mutable index (``index/mutable.py``): the host keeps only the cheap
    vectorized linking.

    ``filter_deleted`` (static; requires ``db.deleted``) excludes
    tombstoned nodes at EVERY layer — new nodes must never link to the
    dead. The one-shot wave builder passes False (a fresh build has no
    tombstone bitmap; not-yet-inserted rows are unreachable, nothing
    links to them).

    ``ef_upper`` (static) narrows the beam at layers above 0: the
    upper-layer beam mostly supplies DESCENT seeds (only the ~1/M
    fraction of nodes with level >= 1 link there), and the sequential
    oracle descends with ef=1 — a beam between those extremes trades a
    little upper-layer candidate richness for the probe wall-clock the
    beam's ~ef expansion steps cost at every layer. None keeps the full
    ``ef`` everywhere. Returns ([L, B, ef] dists, [L, B, ef] ids),
    bottom layer FIRST (out[l] = layer l); upper-layer rows are padded
    to ef width with INF/-1 when ``ef_upper`` trims them."""
    B = queries.shape[0]
    ep = jnp.broadcast_to(
        jnp.asarray(db.entry, jnp.int32).reshape(()), (B, 1))
    ep_d = ops.dist_h(jnp.take(db.high, ep, axis=0), queries)
    # the wave build's snapshot (identity filter, no tombstones) needs
    # no visited bitmap: at 2048 queries its per-step scatter is the
    # probe's largest op on a TPU, and its state is [B, N/32] words
    bitmap = filter_deleted or db.filter_kind != "none"
    out_d, out_i = [], []
    for layer in range(len(db.layers) - 1, -1, -1):
        ef_l = ef if layer == 0 else min(ef_upper or ef, ef)
        fd, fi, _, _ = search_layer_batched(
            db, layer, queries, qprep, ep_d, ep, ef=ef_l, k=k,
            max_steps=2 * ef_l + 16, filter_deleted=filter_deleted,
            bitmap=bitmap)
        ep_d, ep = fd, fi
        if ef_l < ef:
            fd = jnp.pad(fd, ((0, 0), (0, ef - ef_l)),
                         constant_values=INF)
            fi = jnp.pad(fi, ((0, 0), (0, ef - ef_l)),
                         constant_values=-1)
        out_d.append(fd)
        out_i.append(fi)
    return jnp.stack(out_d[::-1]), jnp.stack(out_i[::-1])


@functools.partial(jax.jit, static_argnames=("ef0", "k_schedule",
                                             "deferred", "rerank_mult",
                                             "promote_mult"))
def _search_batched_jit(db, queries, qprep, ef0, k_schedule, deferred,
                        rerank_mult, promote_mult):
    return _search_batched_impl(db, queries, qprep, ef0=ef0,
                                k_schedule=k_schedule, deferred=deferred,
                                rerank_mult=rerank_mult,
                                promote_mult=promote_mult)


def search_batched(db: PackedDB, queries, qprep=None, *, pca=None,
                   filt=None,
                   ef0: Optional[int] = None,
                   k_schedule: Optional[Tuple[int, ...]] = None,
                   entry: Optional[int] = None,
                   return_stats: bool = False,
                   deferred: Optional[bool] = None,
                   rerank_mult: Optional[int] = None,
                   promote_mult: Optional[int] = None):
    """Full multi-layer pHNSW search for a batch (jit'd).
    queries: [B, D] (device). Returns (dists [B, ef0], idx [B, ef0]);
    with ``return_stats=True`` also a dict with per-query telemetry:
    ``steps_per_layer`` [n_layers, B] (top layer first), ``steps_total``
    [B] and ``dist_h_evals`` [B] (high-dim distance evaluations — the
    quantity deferred re-ranking trades recall against), plus the
    serving-plane accounting pair ``coverage``/``degraded`` (trivially
    1.0/False here; the sharded path reports real values).

    ``qprep`` is the active filter's per-query data; leave it None and
    pass ``filt`` (a ``core.filters.FilterSpec``) or ``pca`` (the
    PCA-filter convenience, the seed API) to compute it here. The
    identity filter needs neither.

    ``deferred`` / ``rerank_mult`` select the re-ranking mode (defaults
    from ``db.cfg.deferred_rerank`` / ``db.cfg.rerank_mult``): deferred
    traverses on filter distances only and re-ranks the final
    ``rerank_mult * ef0`` candidates in high dim with ONE batched
    Dist.H call per query. ``promote_mult`` (cascade + deferred only;
    default ``db.cfg.promote_mult``) widens the layer-0 traversal to
    ``promote_mult * ef0`` PQ-space candidates that the PCA promote
    stage trims back to ``rerank_mult * ef0`` before that single
    Dist.H pass.

    ``entry`` overrides the descent entry point (``db.entry`` by
    default). Both the entry and the tombstone bitmap ``db.deleted`` are
    DATA to the compiled program — changing either between calls never
    recompiles."""
    if filt is not None and filt.kind != db.filter_kind:
        raise ValueError(f"filter mismatch: db carries a "
                         f"{db.filter_kind!r} payload, filt is "
                         f"{filt.kind!r}")
    if qprep is None:
        if filt is not None:
            qprep = filt.prepare_jnp(queries)
        elif pca is not None:
            qprep = pca.transform_jnp(queries).astype(jnp.float32)
        elif db.filter_kind == "none":
            qprep = queries[:, :0].astype(jnp.float32)
        else:
            raise ValueError("qprep, filt or pca required for the "
                             f"{db.filter_kind!r} filter")
    if entry is not None:
        db = dataclasses.replace(db, entry=entry)
    if deferred is None:
        deferred = db.cfg.deferred_rerank
    if rerank_mult is None:
        rerank_mult = db.cfg.rerank_mult
    if promote_mult is None:
        promote_mult = db.cfg.promote_mult
    # normalize the no-op combinations BEFORE they key the jit cache:
    # deferred is defined as a no-op for the identity filter,
    # rerank_mult only exists inside deferred mode, and promote_mult
    # only exists for the deferred cascade — without this a caller
    # varying any knob recompiles a bit-identical program
    if db.filter_kind == "none":
        deferred = False
    if not deferred:
        rerank_mult = 1
    if not (deferred and db.filter_kind == "cascade"):
        promote_mult = 1
    else:
        # the promote pool can never be narrower than the rerank pool
        promote_mult = max(int(promote_mult), int(rerank_mult))
    fd, fi, steps, dhe = _search_batched_jit(
        db, queries, qprep, ef0 or db.cfg.ef0,
        k_schedule or db.cfg.k_schedule_for(db.filter_kind,
                                            bool(deferred)),
        bool(deferred), int(rerank_mult), int(promote_mult))
    if return_stats:
        # coverage/degraded ride along so the stats contract is uniform
        # with the sharded degraded-mode path (core/distributed.py):
        # a single-shard snapshot always reaches its whole live set
        return fd, fi, {"steps_per_layer": steps,
                        "steps_total": steps.sum(axis=0),
                        "dist_h_evals": dhe,
                        "coverage": 1.0, "degraded": False}
    return fd, fi


def _search_batched_impl(db: PackedDB, queries, qprep, *,
                         ef0: Optional[int] = None,
                         k_schedule: Optional[Tuple[int, ...]] = None,
                         deferred: bool = False, rerank_mult: int = 1,
                         promote_mult: int = 1,
                         final_rerank: bool = True):
    """The traced body (also called directly inside shard_map by
    ``core/distributed.py``). The upper routing layers never filter
    tombstones — a deleted node is a fine descent waypoint — the output
    layer (0) does, iff the db carries a bitmap.

    Deferred mode runs the whole descent in filter space (the entry is
    scored against the payload, every layer traverses on filter
    distances, layer 0 keeps ``rerank_mult * ef0`` candidates) and
    finishes with a single batched Dist.H over the final list. The
    deferred CASCADE widens layer 0 further to ``promote_mult * ef0``
    PQ-space candidates and inserts the PCA promote stage (one batched
    ``dist_l`` over side-car rows, once per query — never per step)
    that trims them back to ``rerank_mult * ef0`` before the Dist.H
    pass. ``final_rerank=False`` (deferred only) skips promote AND
    re-rank and returns the WIDE filter-space list instead — the
    sharded path merges per-shard lists on filter distances first and
    runs promote + re-rank ONCE globally after the cross-shard merge."""
    cfg = db.cfg
    B = queries.shape[0]
    ks = k_schedule or cfg.k_schedule_for(db.filter_kind,
                                          bool(deferred))
    k_of = lambda l: ks[min(l, len(ks) - 1)]
    ep = jnp.broadcast_to(
        jnp.asarray(db.entry, jnp.int32).reshape(()), (B, 1))
    deferred = deferred and db.filter_kind != "none"
    cascade = deferred and db.filter_kind == "cascade"
    if deferred:
        pay = jnp.take(db.low, ep, axis=0)              # [B, 1, P]
        if db.filter_kind == "pca":
            ep_d = ops.dist_l(pay, qprep)
        elif db.filter_kind == "cascade":
            ep_d = ops.pq_adc(pay, _cascade_lut(qprep, pay.shape[-1]))
        else:
            ep_d = ops.pq_adc(pay, qprep)
        dhe = jnp.zeros((B,), jnp.int32)
    else:
        ep_d = ops.dist_h(jnp.take(db.high, ep, axis=0), queries)
        dhe = jnp.ones((B,), jnp.int32)
    n_layers = len(db.layers)
    steps = []
    for layer in range(n_layers - 1, 0, -1):
        ep_d, ep, st, de = search_layer_batched(
            db, layer, queries, qprep, ep_d, ep,
            ef=cfg.ef_for_layer(layer), k=k_of(layer), deferred=deferred)
        steps.append(st)
        dhe = dhe + de
    ef_out = ef0 or cfg.ef0
    wide_mult = promote_mult if cascade else rerank_mult
    ef_run = ef_out * wide_mult if deferred else ef_out
    fd, fi, st, de = search_layer_batched(
        db, 0, queries, qprep, ep_d, ep, ef=ef_run, k=k_of(0),
        filter_deleted=db.deleted is not None, deferred=deferred)
    steps.append(st)
    dhe = dhe + de
    if deferred and final_rerank:
        if cascade:
            # promote stage: ONE batched PCA score over side-car rows
            # trims the PQ-space pool to the Dist.H rerank pool
            ok = fi >= 0
            mid = jnp.take(db.low2, jnp.maximum(fi, 0), axis=0)
            qpca = _cascade_qpca(qprep, db.low.shape[-1])
            dm = jnp.where(ok, ops.dist_l(mid, qpca), INF)
            pd, pi = _rank_sort_with_payload(dm, jnp.where(ok, fi, -1))
            fd, fi = pd[:, :ef_out * rerank_mult], \
                pi[:, :ef_out * rerank_mult]
        # the deferred high-dim re-rank: ONE batched Dist.H over the
        # final filter-space list, then a single sort back to ef0
        ok = fi >= 0
        xh = jnp.take(db.high, jnp.maximum(fi, 0), axis=0)
        dh = jnp.where(ok, ops.dist_h(xh, queries), INF)
        dhe = dhe + ok.sum(axis=1, dtype=jnp.int32)
        rd, ri = _rank_sort_with_payload(dh, jnp.where(ok, fi, -1))
        fd, fi = rd[:, :ef_out], ri[:, :ef_out]
    return fd, fi, jnp.stack(steps), dhe


# ---------------------------------------------------------------------------
# slotted resumable search state — the continuous-batching substrate
# (serve/scheduler.py; DESIGN.md § Serving front-end).
#
# The synchronous path runs descent + layer 0 to completion for one
# batch and returns; a slot whose ``done`` mask latched early then idles
# until the SLOWEST query in the batch converges (the convoy). Here the
# layer-0 traversal state is instead a long-lived pytree of S slots:
#
#   * ``_slot_step_jit`` advances EVERY live slot by up to ``quantum``
#     expansion iterations of the SAME ``_layer_body`` program the
#     synchronous search compiles, and returns — the host can now
#     retire slots whose ``done`` latched and refill them;
#   * ``_slot_admit_jit`` swaps freshly-descended queries into chosen
#     slots as PURE DATA (a fixed-width scatter; unused admission rows
#     carry an out-of-range slot id and are dropped) — the same
#     zero-recompile discipline as entry/tombstone swaps;
#   * per-slot ``ef_eff`` (mixed-k / adaptive-ef) and ``budget``
#     (adaptive step budgets) ride in the state as data — see
#     ``_layer_body``.
#
# Sharded twins vmap the identical per-shard program over the stacked
# ShardedDB leaves; the host merges per-shard lists at retirement
# (shards are disjoint, so the merge is a host-side sorted concat).
# ---------------------------------------------------------------------------

@dataclass
class SlotState:
    """The resumable layer-0 traversal state of S slots — every field
    is pytree DATA (leading dim S; the sharded twin prepends the shard
    dim P), so admission, budget escalation, and epoch swaps never
    recompile. Geometry (CAP/ef/k widths) is fixed at
    ``make_slot_state`` time and keys the compiled programs via shapes.

    An EMPTY slot is ``done=True`` with ``budget=0`` and a ``-1``/INF
    frontier: it latches immediately, gates no loop iteration, and its
    masked lanes cost only vector width."""
    C_d: jax.Array      # [S, CAP] sorted candidate frontier dists
    C_i: jax.Array      # [S, CAP] candidate ids (-1 pad)
    F_d: jax.Array      # [S, EF] sorted result dists
    F_i: jax.Array      # [S, EF] result ids (-1 pad)
    V: jax.Array        # [S, ceil(N/32)] visited bitmap words
    Cp: jax.Array       # [S, k] C_pca threshold heap
    done: jax.Array     # [S] bool, latched per slot
    nsteps: jax.Array   # [S] int32 expansion steps so far
    dhe: jax.Array      # [S] int32 Dist.H evaluations so far
    q_high: jax.Array   # [S, D] the resident queries
    qprep: jax.Array    # [S, ...] per-query filter prep (payload space)
    ef_eff: jax.Array   # [S] int32 per-slot effective ef (<= EF)
    budget: jax.Array   # [S] int32 per-slot expansion-step budget


jax.tree_util.register_dataclass(
    SlotState,
    data_fields=["C_d", "C_i", "F_d", "F_i", "V", "Cp", "done", "nsteps",
                 "dhe", "q_high", "qprep", "ef_eff", "budget"],
    meta_fields=[])


def _slot_geometry(db: PackedDB, ef: int,
                   deferred: bool = False) -> Tuple[int, int, int]:
    """(k, W, CAP) of the slotted layer-0 program — derived exactly the
    way ``search_layer_batched`` derives them, so the slotted body is
    the same compiled shape family as the synchronous one.
    ``deferred`` selects the same effective layer-0 k the synchronous
    default does (the deferred cascade runs unpruned at M0)."""
    cfg = db.cfg
    k = cfg.k_schedule_for(db.filter_kind, deferred)[0]
    W = cfg.expand_width
    M = db.layers[0].adj.shape[-1]
    kk = W * M if db.filter_kind == "none" else W * k
    return k, W, max(ef + kk, 8)


def make_slot_state(db: PackedDB, n_slots: int, qprep_example, *,
                    ef: int, n_shards: Optional[int] = None,
                    deferred: bool = False) -> SlotState:
    """An all-empty slot bank. ``ef`` is the COMPILED result width (the
    per-slot ``ef_eff`` can only narrow it — size it to the largest k /
    ef any request may ask for). ``qprep_example`` is any [b, ...]
    filter-prep array, used only for its trailing shape/dtype.
    ``n_shards`` (sharded serving) prepends the shard dim to every
    leaf — the stacked per-shard states the vmapped twins advance.
    ``deferred`` must match the mode the slots will step in — it sizes
    the Cp register (the per-expansion keep width) to the same
    effective k the synchronous program uses."""
    k, _, CAP = _slot_geometry(db, ef, deferred)
    N = db.high.shape[-2]
    D = db.high.shape[-1]
    nw = -(-N // 32)
    lead = () if n_shards is None else (n_shards,)
    shp = lambda *s: lead + (n_slots,) + s
    qp_trail = tuple(np.asarray(qprep_example).shape[1:])
    return SlotState(
        C_d=jnp.full(shp(CAP), INF),
        C_i=jnp.full(shp(CAP), -1, jnp.int32),
        F_d=jnp.full(shp(ef), INF),
        F_i=jnp.full(shp(ef), -1, jnp.int32),
        V=jnp.zeros(shp(nw), jnp.int32),
        Cp=jnp.full(shp(k), INF),
        done=jnp.ones(shp(), bool),
        nsteps=jnp.zeros(shp(), jnp.int32),
        dhe=jnp.zeros(shp(), jnp.int32),
        q_high=jnp.zeros(shp(D), jnp.float32),
        qprep=jnp.zeros(shp(*qp_trail), jnp.float32),
        ef_eff=jnp.full(shp(), ef, jnp.int32),
        budget=jnp.zeros(shp(), jnp.int32),
    )


def _slot_admit_impl(db: PackedDB, state: SlotState, q_new, qprep_new,
                     slot_ids, ef_eff_new, budget_new, *,
                     deferred: bool = False) -> SlotState:
    """Descend the admission batch through the routing layers (the same
    per-layer programs as ``_search_batched_impl``) and scatter the
    fresh layer-0 state into the chosen slots. The admission width is
    FIXED (pad rows carry slot id >= S and are dropped by the scatter),
    so every admission reuses one compiled program regardless of how
    many slots actually refill.

    ``deferred`` (static) admits in FILTER space exactly the way the
    synchronous deferred path does: the entry is scored against the
    payload and the routing descent traverses on filter distances, so
    the scattered layer-0 state is bit-identical to the synchronous
    program's."""
    cfg = db.cfg
    ef = state.F_d.shape[-1]
    k, _, CAP = _slot_geometry(db, ef, deferred)
    ks = cfg.k_schedule_for(db.filter_kind, deferred)
    k_of = lambda l: ks[min(l, len(ks) - 1)]
    A = q_new.shape[0]
    ep = jnp.broadcast_to(
        jnp.asarray(db.entry, jnp.int32).reshape(()), (A, 1))
    deferred = deferred and db.filter_kind != "none"
    if deferred:
        pay = jnp.take(db.low, ep, axis=0)
        if db.filter_kind == "pca":
            ep_d = ops.dist_l(pay, qprep_new)
        elif db.filter_kind == "cascade":
            ep_d = ops.pq_adc(pay, _cascade_lut(qprep_new,
                                                pay.shape[-1]))
        else:
            ep_d = ops.pq_adc(pay, qprep_new)
        dhe = jnp.zeros((A,), jnp.int32)
    else:
        ep_d = ops.dist_h(jnp.take(db.high, ep, axis=0), q_new)
        dhe = jnp.ones((A,), jnp.int32)
    for layer in range(len(db.layers) - 1, 0, -1):
        ep_d, ep, _, de = search_layer_batched(
            db, layer, q_new, qprep_new, ep_d, ep,
            ef=cfg.ef_for_layer(layer), k=k_of(layer),
            deferred=deferred)
        dhe = dhe + de
    C_d, C_i, F_d, F_i, V, Cp = _layer_init(
        db, ep_d, ep, ef=ef, k=k, CAP=CAP,
        filter_deleted=db.deleted is not None)
    ids = slot_ids
    sc = lambda dst, rows: dst.at[ids].set(rows, mode="drop")
    return dataclasses.replace(
        state,
        C_d=sc(state.C_d, C_d), C_i=sc(state.C_i, C_i),
        F_d=sc(state.F_d, F_d), F_i=sc(state.F_i, F_i),
        V=sc(state.V, V), Cp=sc(state.Cp, Cp),
        done=sc(state.done, jnp.zeros((A,), bool)),
        nsteps=sc(state.nsteps, jnp.zeros((A,), jnp.int32)),
        dhe=sc(state.dhe, dhe),
        q_high=sc(state.q_high, q_new),
        qprep=sc(state.qprep, qprep_new),
        ef_eff=sc(state.ef_eff, ef_eff_new),
        budget=sc(state.budget, budget_new))


def _slot_step_impl(db: PackedDB, state: SlotState, *, quantum: int,
                    expand_width: int,
                    deferred: bool = False) -> SlotState:
    """Advance every live slot by up to ``quantum`` iterations of the
    layer-0 body — the SAME ``_layer_body`` the synchronous search
    compiles, with the per-slot ``ef_eff``/``budget`` data
    generalizations active. The loop exits early once no slot can make
    progress (all done or budget-frozen), so a sparse bank costs what
    its live slots cost. ``deferred`` (static) traverses on filter
    distances — the slot's F list then holds FILTER-space candidates
    and the scheduler runs the single batched Dist.H pass at
    retirement."""
    ef = state.F_d.shape[-1]
    k = state.Cp.shape[-1]
    body = _layer_body(db, 0, state.q_high, state.qprep, ef=ef, k=k,
                       W=expand_width, steps=0,
                       filter_deleted=db.deleted is not None,
                       deferred=deferred and db.filter_kind != "none",
                       ef_eff=state.ef_eff,
                       budget=state.budget)
    st = (jnp.int32(0), state.C_d, state.C_i, state.F_d, state.F_i,
          state.V, state.Cp, state.done, state.nsteps, state.dhe)

    def cond(s):
        t, *_, done, ns, _de = s
        return (t < quantum) & (~done & (ns < state.budget)).any()

    out = jax.lax.while_loop(cond, body, st)
    _, C_d, C_i, F_d, F_i, V, Cp, done, nsteps, dhe = out
    return dataclasses.replace(
        state, C_d=C_d, C_i=C_i, F_d=F_d, F_i=F_i, V=V, Cp=Cp,
        done=done, nsteps=nsteps, dhe=dhe)


_slot_admit_jit = jax.jit(_slot_admit_impl,
                          static_argnames=("deferred",))


@functools.partial(jax.jit, static_argnames=("quantum", "expand_width",
                                             "deferred"))
def _slot_step_jit(db, state, quantum, expand_width, deferred=False):
    return _slot_step_impl(db, state, quantum=quantum,
                           expand_width=expand_width, deferred=deferred)


@functools.partial(jax.jit, static_argnames=("deferred",))
def _slot_admit_sharded_jit(db_stack, state, q_new, qprep_new, slot_ids,
                            ef_eff_new, budget_new, deferred=False):
    """Admission over a stacked-leaf PackedDB view of a ShardedDB
    ([P, ...] leaves; ``core.distributed.stacked_db_view``): each shard
    descends its own graph for the SAME queries into the SAME slots."""
    return jax.vmap(
        lambda d, s: _slot_admit_impl(d, s, q_new, qprep_new, slot_ids,
                                      ef_eff_new, budget_new,
                                      deferred=deferred)
    )(db_stack, state)


@functools.partial(jax.jit, static_argnames=("quantum", "expand_width",
                                             "deferred"))
def _slot_step_sharded_jit(db_stack, state, quantum, expand_width,
                           deferred=False):
    return jax.vmap(
        lambda d, s: _slot_step_impl(d, s, quantum=quantum,
                                     expand_width=expand_width,
                                     deferred=deferred)
    )(db_stack, state)


def _slot_step_prefix_impl(db, state, *, width, quantum, expand_width,
                           deferred=False):
    """Step only the first ``width`` slots of the bank — the WIDTH
    LADDER. Slots are allocated low-first, so at partial occupancy the
    scheduler steps the smallest compiled prefix covering the highest
    live slot instead of paying full-bank prices (each ladder rung is
    one compile, warmed at construction — steady state stays
    zero-recompile)."""
    part = jax.tree_util.tree_map(lambda a: a[:width], state)
    part = _slot_step_impl(db, part, quantum=quantum,
                           expand_width=expand_width, deferred=deferred)
    return jax.tree_util.tree_map(lambda f, p: f.at[:width].set(p),
                                  state, part)


@functools.partial(jax.jit,
                   static_argnames=("width", "quantum", "expand_width",
                                    "deferred"))
def _slot_step_prefix_jit(db, state, width, quantum, expand_width,
                          deferred=False):
    return _slot_step_prefix_impl(db, state, width=width,
                                  quantum=quantum,
                                  expand_width=expand_width,
                                  deferred=deferred)


@functools.partial(jax.jit,
                   static_argnames=("width", "quantum", "expand_width",
                                    "deferred"))
def _slot_step_prefix_sharded_jit(db_stack, state, width, quantum,
                                  expand_width, deferred=False):
    return jax.vmap(
        lambda d, s: _slot_step_prefix_impl(d, s, width=width,
                                            quantum=quantum,
                                            expand_width=expand_width,
                                            deferred=deferred)
    )(db_stack, state)


def _slot_admit_step_impl(db, state, q_new, qprep_new, slot_ids,
                          ef_eff_new, budget_new, *, width, quantum,
                          expand_width, deferred=False):
    """One FUSED tick program: admission scatter + prefix step in a
    single compiled call — the same content as the synchronous search
    (upper-layer descent, then the layer-0 loop), so a tick with
    arrivals costs one dispatch and never materializes the
    intermediate post-admission state."""
    state = _slot_admit_impl(db, state, q_new, qprep_new, slot_ids,
                             ef_eff_new, budget_new, deferred=deferred)
    return _slot_step_prefix_impl(db, state, width=width,
                                  quantum=quantum,
                                  expand_width=expand_width,
                                  deferred=deferred)


@functools.partial(jax.jit,
                   static_argnames=("width", "quantum", "expand_width",
                                    "deferred"))
def _slot_admit_step_jit(db, state, q_new, qprep_new, slot_ids,
                         ef_eff_new, budget_new, width, quantum,
                         expand_width, deferred=False):
    return _slot_admit_step_impl(db, state, q_new, qprep_new, slot_ids,
                                 ef_eff_new, budget_new, width=width,
                                 quantum=quantum,
                                 expand_width=expand_width,
                                 deferred=deferred)


@functools.partial(jax.jit,
                   static_argnames=("width", "quantum", "expand_width",
                                    "deferred"))
def _slot_admit_step_sharded_jit(db_stack, state, q_new, qprep_new,
                                 slot_ids, ef_eff_new, budget_new,
                                 width, quantum, expand_width,
                                 deferred=False):
    return jax.vmap(
        lambda d, s: _slot_admit_step_impl(
            d, s, q_new, qprep_new, slot_ids, ef_eff_new, budget_new,
            width=width, quantum=quantum, expand_width=expand_width,
            deferred=deferred)
    )(db_stack, state)


@jax.jit
def _retire_rerank_jit(db, queries, fi):
    """The scheduler's deferred Dist.H retirement pass: the EXACT final
    block of the synchronous deferred program (one batched Dist.H over
    the filter-space list, then the same stable rank sort) applied to a
    fixed-width batch of retiring slots — non-retiring pad rows carry
    ``fi = -1`` everywhere and cost only masked lanes. Bit-parity with
    ``run_stream_sync`` depends on this being the same op sequence."""
    ok = fi >= 0
    xh = jnp.take(db.high, jnp.maximum(fi, 0), axis=0)
    dh = jnp.where(ok, ops.dist_h(xh, queries), INF)
    rd, ri = _rank_sort_with_payload(dh, jnp.where(ok, fi, -1))
    return rd, ri, ok.sum(axis=1, dtype=jnp.int32)


@jax.jit
def _retire_promote_jit(db, qprep, fi, n_keep):
    """The scheduler's cascade promote pass at retirement: PCA-score
    the side-car rows of the retiring slots' PQ-space lists and keep
    each slot's best ``n_keep`` (data, per-slot) — the slotted twin of
    the promote stage in ``_search_batched_impl``."""
    ok = fi >= 0
    mid = jnp.take(db.low2, jnp.maximum(fi, 0), axis=0)
    qpca = _cascade_qpca(qprep, db.low.shape[-1])
    dm = jnp.where(ok, ops.dist_l(mid, qpca), INF)
    pd, pi = _rank_sort_with_payload(dm, jnp.where(ok, fi, -1))
    keep = jnp.arange(pd.shape[1])[None, :] < n_keep[:, None]
    return jnp.where(keep, pd, INF), jnp.where(keep, pi, -1)


def slot_cache_sizes() -> Tuple[int, ...]:
    """(step, admit, step_sharded, admit_sharded, step_prefix,
    step_prefix_sharded, admit_step, admit_step_sharded,
    retire_rerank, retire_promote, the device query prep)
    compiled-program cache sizes — the scheduler's
    zero-recompile-under-churn assertions read these (same pattern as
    ``core.distributed.search_cache_sizes``)."""
    return (_slot_step_jit._cache_size(),
            _slot_admit_jit._cache_size(),
            _slot_step_sharded_jit._cache_size(),
            _slot_admit_sharded_jit._cache_size(),
            _slot_step_prefix_jit._cache_size(),
            _slot_step_prefix_sharded_jit._cache_size(),
            _slot_admit_step_jit._cache_size(),
            _slot_admit_step_sharded_jit._cache_size(),
            _retire_rerank_jit._cache_size(),
            _retire_promote_jit._cache_size(),
            prepare_cache_size())
