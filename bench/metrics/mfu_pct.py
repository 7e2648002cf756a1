"""The whole step's share of the chip's bf16 peak: images per second of
the traced rounds x the operations one image needs, over the peak."""
from bench.counts import image_ops


def read(run):
    if run["trace"] is None:
        return None
    return 100.0 * run["traced_images_per_s"] * image_ops(run["config"]) \
        / run["peaks"]["flops_bf16"]
