"""Input-pipeline throughput: RecordIO -> decode -> augment -> batch.

Host-side: a host rate, so it runs wherever JAX was told to (batches
land on that platform's default device) and names it in every line;
only --train-overlap needs the chip. Measures the framework's image path — the
native C++ batched decoder (+ prefetch overlap) against the pure-PIL
fallback — on a synthetic RecordIO file it writes itself. The reference
framework's equivalent path is the fully-C++ ImageRecordIOParser2
(src/io/iter_image_recordio_2.cc).

    python benchmark/bench_input_pipeline.py [--n 512] [--size 256]

Prints one JSON line per pipeline variant.
"""
import argparse
import io as _io
import json
import os
import shutil
import sys
import tempfile
import time

import jax  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np


def make_recfile(path, n, size):
    from PIL import Image

    import mxnet_tpu as mx

    rec = mx.recordio.MXIndexedRecordIO(path + ".idx", path + ".rec",
                                        "w")
    rng = np.random.RandomState(0)
    for i in range(n):
        img = Image.fromarray(
            rng.randint(0, 255, (size, size, 3), np.uint8))
        buf = _io.BytesIO()
        img.save(buf, format="JPEG", quality=90)
        header = mx.recordio.IRHeader(0, float(i % 10), i, 0)
        rec.write_idx(i, mx.recordio.pack(header, buf.getvalue()))
    rec.close()


def run(path, n, batch_size, variant, threads=4):
    import mxnet_tpu as mx
    from mxnet_tpu import image as mx_image

    from mxnet_tpu import config

    config.set_override("MXNET_NATIVE_IMAGE", variant != "pil")
    it = mx_image.ImageIter(
        batch_size, (3, 224, 224), path_imgrec=path + ".rec",
        path_imgidx=path + ".idx", resize=256, rand_crop=True,
        rand_mirror=True, num_threads=threads)
    if variant == "native+prefetch":
        from mxnet_tpu import io
        it = io.PrefetchingIter(it)

    # warmup epoch (decoder pools spin up, buffers allocate)
    for _ in it:
        pass
    it.reset()
    t0 = time.time()
    count = 0
    for batch in it:
        count += batch.data[0].shape[0]
    dt = time.time() - t0
    return count / dt


def run_train_overlap(path, n, batch_size, threads):
    """Decode -> PrefetchingIter -> ResNet-50 TrainStep: the end-to-end
    feed test (reference identity: iter_image_recordio_2.cc keeping
    GPUs busy). Reports NET training img/s with the pipeline in the
    loop; compare against the synthetic-batch bench.py number to see
    whether the host feeds the device. Needs the chip."""
    import mxnet_tpu as mx
    from mxnet_tpu import image as mx_image, io, models
    from mxnet_tpu.initializer import Xavier
    from mxnet_tpu.parallel import make_train_step

    sym = models.get_symbol(network="resnet", num_layers=50,
                            num_classes=1000, image_shape=(3, 224, 224))
    step = make_train_step(
        sym, optimizer="sgd",
        optimizer_params={"momentum": 0.9,
                          "rescale_grad": 1.0 / batch_size},
        compute_dtype="bfloat16")
    state = step.init_state(Xavier(factor_type="in", magnitude=2.0),
                            {"data": (batch_size, 3, 224, 224),
                             "softmax_label": (batch_size,)})
    rng = jax.random.PRNGKey(0)

    it = io.PrefetchingIter(mx_image.ImageIter(
        batch_size, (3, 224, 224), path_imgrec=path + ".rec",
        path_imgidx=path + ".idx", resize=256, rand_crop=True,
        rand_mirror=True, num_threads=threads))

    def consume(batch):
        nonlocal state
        vals = {"data": batch.data[0].asnumpy(),
                "softmax_label":
                    np.asarray(batch.label[0].asnumpy(),
                               np.float32).reshape(-1)}
        state, outs = step(state, step.place_batch(vals), 0.1, rng)
        return outs

    # warmup: compile + decoder spin-up
    outs = consume(next(it))
    jax.block_until_ready(outs[0])
    it.reset()
    t0 = time.time()
    count = 0
    for batch in it:
        outs = consume(batch)
        count += batch_size
    jax.block_until_ready(outs)
    return count / (time.time() - t0)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=512)
    ap.add_argument("--size", type=int, default=256,
                    help="stored JPEG side length")
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--threads", type=int, default=4)
    ap.add_argument("--train-overlap", action="store_true",
                    help="feed a bf16 ResNet-50 TrainStep from the "
                         "pipeline and report net img/s (use on a "
                         "TPU-attached host)")
    args = ap.parse_args()
    from bench_common import device_fields, require_accelerator
    dev = require_accelerator("bench_input_pipeline.py "
                              "--train-overlap") \
        if args.train_overlap else device_fields()

    d = tempfile.mkdtemp()
    try:
        path = os.path.join(d, "bench")
        make_recfile(path, args.n, args.size)

        if args.train_overlap:
            rate = run_train_overlap(path, args.n, args.batch_size,
                                     args.threads)
            print(json.dumps({
                "metric": "input_pipeline_train_overlap",
                "value": round(rate, 1), "unit": "img/s",
                "threads": args.threads, "batch": args.batch_size,
                **dev}))
            return

        results = {}
        for variant in ("pil", "native", "native+prefetch"):
            rate = run(path, args.n, args.batch_size, variant,
                       args.threads)
            results[variant] = rate
            print(json.dumps({
                "metric": "input_pipeline_throughput",
                "variant": variant,
                "value": round(rate, 1),
                "unit": "img/s",
                "threads": args.threads,
                "batch": args.batch_size,
                "vs_pil": round(rate / results["pil"], 2), **dev}))
    finally:
        shutil.rmtree(d, ignore_errors=True)


if __name__ == "__main__":
    main()
