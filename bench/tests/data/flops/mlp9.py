"""Forward FLOPs per sample of the 9-unit split MLP, from its shapes."""


def forward_flops_per_sample(cfg) -> int:
    d, w, u = cfg["dim"], cfg["width"], cfg["n_units"]
    return 2 * d * w + 2 * w * w * (u - 1) + 2 * w * cfg["n_classes"]
