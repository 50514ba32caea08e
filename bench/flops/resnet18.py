"""Forward FLOPs per sample of the CIFAR ResNet18, from its shapes.

Counts the multiply-adds of every convolution (3x3 and the 1x1
projections) and of the head, at two FLOPs each; BatchNorm, ReLU and the
pooling add under 1%.  32x32x3 inputs: 1.11 GFLOP per sample, of which
0.96 fall inside the image and the rest on the convolutions' zero
padding.  Widths and strides are the configuration's."""


def _taps(size: int, k: int, stride: int, padding: bool) -> int:
    """Kernel taps summed over the outputs along one spatial axis of a
    SAME convolution; without ``padding`` the taps that fall on the zero
    padding are left out."""
    out = -(-size // stride)
    if padding:
        return out * k
    lo = max((out - 1) * stride + k - size, 0) // 2
    return sum(1 for o in range(out) for t in range(k)
               if 0 <= o * stride - lo + t < size)


def forward_flops_per_sample(cfg, count_padding: bool = True) -> int:
    """Two FLOPs per multiply-add.  ``count_padding=False`` counts only the
    taps inside the image, as XLA's cost analysis does."""
    hw, cin = cfg["image_size"], cfg["channels"]
    t = lambda size, k, s: _taps(size, k, s, count_padding) ** 2
    stem = cfg["stem_channels"]
    f = 2 * t(hw, 3, 1) * cin * stem                # stem
    cin = stem
    for cout, stride in zip(cfg["stage_channels"], cfg["stage_strides"]):
        out = -(-hw // stride)
        f += 2 * t(hw, 3, stride) * cin * cout      # conv1 (strided)
        f += 2 * t(out, 3, 1) * cout * cout         # conv2
        if stride != 1 or cin != cout:
            f += 2 * t(hw, 1, stride) * cin * cout  # 1x1 projection
        hw, cin = out, cout
    return f + 2 * cin * cfg["n_classes"]
