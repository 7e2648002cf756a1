"""MobileNet-V2 (Sandler et al., arXiv:1801.04381, Table 2), as a layer
table.

3x3/2 stem, inverted residual bottlenecks (t, c, n, s) as in the
table, no expansion conv where t = 1, a residual add without activation
where the stride is 1 and the widths match, a 1x1 conv to the last
width, global average pool, fully connected classifier. Widths, depths
and strides come from the configuration file.
"""
from bench.reference import Layer, out_hw


def layers(cfg: dict) -> list[Layer]:
    hw, cin = cfg["image_size"], cfg["stem_width"]
    net = [Layer("conv1", "conv", 3, cin, 3, 2, hw)]
    hw = out_hw(hw, 2)
    for si, (t, c, n, s) in enumerate(cfg["bottlenecks_t_c_n_s"]):
        for bi in range(n):
            stride = s if bi == 0 else 1
            pre, block_in, mid = f"s{si}b{bi}", net[-1].name, cin * t
            ho = out_hw(hw, stride)
            if t != 1:
                net.append(Layer(f"{pre}_exp", "conv", cin, mid, 1, 1, hw))
            net += [Layer(f"{pre}_dw", "dw", mid, mid, 3, stride, hw),
                    Layer(f"{pre}_pj", "conv", mid, c, 1, 1, ho,
                          relu=False)]
            if stride == 1 and cin == c:
                net.append(Layer(f"{pre}_add", "add", c, c, hw=ho,
                                 relu=False, residual=block_in))
            cin, hw = c, ho
    last = cfg["last_width"]
    return net + [Layer("conv_last", "conv", cin, last, 1, 1, hw),
                  Layer("avgpool", "avgpool", last, last, hw, hw=hw),
                  Layer("fc", "fc", last, cfg["num_classes"], relu=False)]
