"""Share of its roofline reached by the PQ ADC filter kernel (one-hot
ADC, mask, threshold and kSort.L in one kernel), over the traced slice
(``roofline.share``, role ``filter``). Layer: kernels."""
from bench import roofline


def read(run):
    return roofline.share(run, "filter", "pq_adc_expand")
