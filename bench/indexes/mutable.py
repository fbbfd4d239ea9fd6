"""A live index: ``MutableIndex.build`` (filter fit, wave build,
publish), served by ``VectorSearchService`` as a live index is."""


def service(cfg, x, seed, pc):
    from repro.index import MutableIndex
    from repro.serve.vector_service import VectorSearchService
    return VectorSearchService(MutableIndex.build(x, pc, seed=seed),
                               batch_size=int(cfg["n_slots"]))
