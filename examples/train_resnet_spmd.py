"""Workload config #2, TPU-native: ResNet over a device mesh with the
compiled SPMD TrainStep (dp x tp mesh, bf16 compute, f32 master
weights) — the path the `resnet-50.train` cell of BENCHMARK.json
measures. Runs on any device count:
`JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
   python examples/train_resnet_spmd.py --num-devices 8`
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import argparse

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu.models import resnet
from mxnet_tpu.parallel import make_mesh, make_train_step


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--num-layers", type=int, default=18)
    p.add_argument("--image-size", type=int, default=32)
    p.add_argument("--batch-size", type=int, default=64)
    p.add_argument("--num-classes", type=int, default=10)
    p.add_argument("--num-devices", type=int, default=1)
    p.add_argument("--model-axis", type=int, default=1)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--dtype", default="bfloat16",
                   choices=["bfloat16", "float32"])
    p.add_argument("--zero1", action="store_true",
                   help="shard optimizer state 1/N over the data axis "
                        "(reduce-scatter -> update -> all-gather)")
    args = p.parse_args()

    import jax
    mesh = None
    if args.num_devices > 1:
        mesh = make_mesh({"data": args.num_devices // args.model_axis,
                          "model": args.model_axis},
                         devices=jax.devices()[:args.num_devices])

    sym = resnet.get_symbol(num_classes=args.num_classes,
                            num_layers=args.num_layers,
                            image_shape=(3, args.image_size,
                                         args.image_size))
    step = make_train_step(
        sym, optimizer="sgd",
        optimizer_params={"momentum": 0.9, "wd": 1e-4},
        mesh=mesh,
        compute_dtype=None if args.dtype == "float32" else args.dtype,
        optimizer_sharding="zero1" if args.zero1 else None)

    shapes = {"data": (args.batch_size, 3, args.image_size,
                       args.image_size),
              "softmax_label": (args.batch_size,)}
    state = step.init_state(mx.init.Xavier(factor_type="in",
                                           magnitude=2.0), shapes)
    rng = jax.random.PRNGKey(0)
    X = np.random.RandomState(0).randn(*shapes["data"]) \
        .astype(np.float32)
    y = np.random.RandomState(1).randint(
        0, args.num_classes, shapes["softmax_label"]).astype(np.float32)
    batch = step.place_batch({"data": X, "softmax_label": y})

    import time
    state, outs = step(state, batch, 0.1, rng)     # compile
    np.asarray(jax.device_get(outs[0][0, 0]))
    t0 = time.time()
    for _ in range(args.steps):
        state, outs = step(state, batch, 0.1, rng)
    np.asarray(jax.device_get(outs[0][0, 0]))
    dt = (time.time() - t0) / args.steps
    print("step %.2f ms  ->  %.0f img/s" % (dt * 1e3,
                                            args.batch_size / dt))


if __name__ == "__main__":
    main()
