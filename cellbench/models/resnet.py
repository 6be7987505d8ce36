"""Builds the program's training objects for a ResNet-family
configuration through the entry points a user calls:
`models.get_symbol(network="resnet", ...)` -> `make_train_step` ->
`TrainStep.fit` over an `io.NDArrayIter`. Weights and data come from
the benchmark (`cellbench.reference.resnet`)."""
import mxnet_tpu as mx
from mxnet_tpu import models
from mxnet_tpu.parallel import make_train_step

from cellbench.reference import resnet as ref


def build_step(cfg, traffic, mesh=None):
    side = int(cfg["image_size"])
    sym = models.get_symbol(network="resnet",
                            num_layers=int(cfg["num_layers"]),
                            image_shape=(3, side, side),
                            num_classes=int(cfg["num_classes"]))
    want = ref.param_shapes(cfg)
    names = [n for n in sym.list_arguments()
             if n not in ("data", "softmax_label")]
    if sorted(names) != sorted(want):
        raise ValueError("the program's parameters differ from the "
                         "reference's: %r" % sorted(
                             set(names) ^ set(want)))
    return make_train_step(
        sym, optimizer="sgd",
        optimizer_params={"momentum": float(traffic["momentum"]),
                          "wd": float(traffic["weight_decay"])},
        compute_dtype=cfg["compute_dtype"], mesh=mesh)


def build_feed(data, label, traffic):
    batch = int(traffic["batch_per_chip"]) * int(traffic.get("chips", 1))
    return mx.io.NDArrayIter(mx.nd.array(data), mx.nd.array(label),
                             batch_size=batch)


def metric():
    return mx.metric.CrossEntropy()
