"""Share of its roofline reached by the Dist.H re-rank kernel, over
the traced slice (``roofline.share``, role ``dist_h``). Layer: kernels."""
from bench import roofline


def read(run):
    return roofline.share(run, "dist_h", "dist_h")
