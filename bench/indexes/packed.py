"""A frozen snapshot: the graph from ``build_hnsw``, the filter fitted
on it (PQ codebooks weighted by graph level) and packed by
``build_packed``, served by ``VectorSearchService``."""


def service(cfg, x, seed, pc):
    from repro.core.filters import make_filter
    from repro.core.graph import build_hnsw
    from repro.core.search_jax import build_packed
    from repro.serve.vector_service import VectorSearchService
    g = build_hnsw(x, pc, seed=seed)
    filt = make_filter(pc, x, seed=seed, levels=g.levels)
    return VectorSearchService(build_packed(g, filt=filt), filt=filt,
                               batch_size=int(cfg["n_slots"]))
