"""Share of its roofline reached by the PCA filter kernel (Dist.L, mask,
threshold and kSort.L in one kernel), over the traced slice
(``roofline.share``, role ``filter``). Layer: kernels."""
from bench import roofline


def read(run):
    return roofline.share(run, "filter", "fused_expand")
