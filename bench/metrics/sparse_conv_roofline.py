"""Share of its roofline that the ``sparse_conv`` kernel reached in the
traced window: the least time of its calls over their device time."""
from bench.counts import roofline_pct


def read(run):
    return roofline_pct(run, "sparse_conv")
