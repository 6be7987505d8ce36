"""What one training step's convolutions need, from shapes alone, for
the roofline of the device operations lowered under `op.Convolution`
(`cellbench/readers/device_kinds.py`). The classifier is no convolution
and is left out; everything else `cellbench/ops/resnet.py` counts is
here, under the same convention: three passes a step (forward, the
gradient by the input, the gradient by the weight), two floating-point
operations a multiply-add. That convention also counts a gradient by the
input image for `conv0`, which nothing needs (1% of the total): kept so
that this need and `model_flops_util`'s are the same operations.

Bytes: each pass reads its two operands and writes its result once, in
the compute dtype's two bytes (forward x, w -> y; by input dy, w -> dx; by
weight x, dy -> dw), so a convolution moves 3 x (input map + output map +
weight) a step. What lies between two convolutions (batch norm, ReLU, the
residual sums) is not counted, fused into a convolution or not.

`convs_step_need` returns the two totals and the roofline takes the
larger of operations over the peak rate and bytes over the peak
bandwidth. For ResNet-50 at batch 256 on a v5e (197 TFLOP/s, 819 GB/s)
the **bytes** bound: 33.6 GB = 41.0 ms against 6.28 TFLOP = 31.9 ms (1x1
convolutions of 64-512 channels do 50-200 operations a byte, the chip
240). The reader prints both least times beside the reading.
"""
from cellbench.ops.resnet import forward_macs
from cellbench.reference.resnet import param_shapes

BYTES = 2                                         # bfloat16


def conv_layers(cfg):
    """[(parameter name, (o, i, kh, kw), input side, output side)] for
    every convolution, walked as `cellbench/ops/resnet.py` walks them:
    `conv0` strides by 2 and a max pool by 2 again; in the first unit
    of stages 2 to 4 the 3x3 and the shortcut stride by 2."""
    shapes = param_shapes(cfg)
    side = int(cfg["image_size"])
    out = [("conv0_weight", shapes["conv0_weight"], side, side // 2)]
    side //= 4
    for name, shape in shapes.items():
        if not name.startswith("stage") or len(shape) != 4:
            continue
        stage, unit = name.split("_")[:2]
        strides = unit == "unit1" and stage != "stage1"
        if name.endswith("conv1_weight"):
            out.append((name, shape, side, side))
            if strides:
                side //= 2
        elif strides and not name.endswith("conv3_weight"):
            out.append((name, shape, 2 * side, side))
        else:
            out.append((name, shape, side, side))
    return out


def _elements(shape, in_side, out_side):
    o, i, kh, kw = shape
    return i * in_side * in_side + o * out_side * out_side, o * i * kh * kw


def convs_step_need(cfg, traffic):
    """(floating-point operations, bytes) of one step's convolutions
    over the whole batch."""
    batch = int(traffic["batch_per_chip"]) * int(traffic.get("chips", 1))
    shapes = param_shapes(cfg)
    classes, features = shapes["fc1_weight"]
    macs = forward_macs(cfg) - classes * features
    maps = weights = 0
    for _name, shape, in_side, out_side in conv_layers(cfg):
        m, w = _elements(shape, in_side, out_side)
        maps += m
        weights += w
    return 3 * 2 * macs * batch, 3 * BYTES * (maps * batch + weights)

