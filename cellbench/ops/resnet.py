"""Operations per sample for the ResNet family, from shapes alone:
the multiply-adds of every convolution and the classifier, two
floating-point operations each, forward once and backward twice
(gradient by input and by weight). Batch norm, activations, pooling
and the optimizer are not counted; neither is recompute."""
from cellbench.reference.resnet import param_shapes


def _conv_macs(shape, out_side):
    o, i, kh, kw = shape
    return o * i * kh * kw * out_side * out_side


def forward_macs(cfg):
    """Multiply-adds of one forward pass of one image. In a unit that
    strides (the first of stages 2 to 4), the first 1x1 convolution
    still reads the larger map; the 3x3, the last 1x1 and the shortcut
    write the smaller one."""
    shapes = param_shapes(cfg)
    side = int(cfg["image_size"]) // 2              # conv0: stride 2
    macs = _conv_macs(shapes["conv0_weight"], side)
    side //= 2                                      # max pool: stride 2
    for name, shape in shapes.items():
        if not name.startswith("stage") or len(shape) != 4:
            continue
        stage, unit = name.split("_")[:2]
        if name.endswith("conv1_weight"):
            macs += _conv_macs(shape, side)
            if unit == "unit1" and stage != "stage1":
                side //= 2
        else:
            macs += _conv_macs(shape, side)
    classes, features = shapes["fc1_weight"]
    return macs + classes * features


def train_flops_per_sample(cfg, traffic=None):
    return 3 * 2 * forward_macs(cfg)
