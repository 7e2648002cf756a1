"""ResNet-50 v1 (He et al., arXiv:1512.03385, Table 1), as a layer table.

7x7/2 stem, 3x3/2 max pool, bottleneck stages (1x1, 3x3, 1x1 convs,
the last ``expansion`` times wider), the stride on the first 1x1 conv
of a stage (v1), a 1x1 projection shortcut on each stage's first block,
global average pool, fully connected classifier. Depths and widths come
from the configuration file.
"""
from bench.reference import Layer, out_hw


def layers(cfg: dict) -> list[Layer]:
    hw = cfg["image_size"]
    net = [Layer("conv1", "conv", 3, 64, 7, 2, hw)]
    hw = out_hw(hw, 2)
    net.append(Layer("pool1", "maxpool", 64, 64, 3, 2, hw))
    cin, hw = 64, out_hw(hw, 2)
    for si, (n, mid) in enumerate(zip(cfg["blocks_per_stage"],
                                      cfg["stage_widths"])):
        out = cfg["expansion"] * mid
        for bi in range(n):
            stride = 2 if bi == 0 and si > 0 else 1
            pre, block_in, ho = f"s{si}b{bi}", net[-1].name, out_hw(hw, stride)
            net += [Layer(f"{pre}_c1", "conv", cin, mid, 1, stride, hw),
                    Layer(f"{pre}_c2", "conv", mid, mid, 3, 1, ho),
                    Layer(f"{pre}_c3", "conv", mid, out, 1, 1, ho,
                          relu=False)]
            skip = block_in
            if bi == 0:
                skip = f"{pre}_proj"
                net.append(Layer(skip, "conv", cin, out, 1, stride, hw,
                                 relu=False, src=block_in))
            net.append(Layer(f"{pre}_add", "add", out, out, hw=ho,
                             src=f"{pre}_c3", residual=skip))
            cin, hw = out, ho
    return net + [Layer("avgpool", "avgpool", cin, cin, hw, hw=hw),
                  Layer("fc", "fc", cin, cfg["num_classes"], relu=False)]
