"""Input-pipeline throughput benchmark (round-4 verdict ask #6).

SURVEY hard-part #5 and the M2 gate ("input pipeline not the bottleneck at
LeNet/ResNet scale") need NUMBERS: this tool measures the native-JPEG
RecordIO path — the analog of the reference's ``ImageRecordIOParser2`` with
its N decode threads (src/io/iter_image_recordio_2.cc) — end to end:

  pack synthetic ImageNet-shaped JPEGs into a RecordIO file
    -> ImageRecordIter(decode + short-edge resize + crop + mean/std + NCHW
       batchify, preprocess_threads=T) for T in {1, 2, 4, 8}
    -> imgs/s per thread count

and compares against the consumer it must outrun:

  ResNet-50 train-step imgs/s on THIS host's CPU backend (a lower bound on
  any real accelerator's demand; the artifact records the measured-TPU
  demand too when MODELBENCH provides one).

Prints one JSON line; --json writes the artifact (IOBENCH.json).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def make_dataset(path, n_images, hw=256, quality=90):
    """Pack n synthetic photos (noise + gradients compress like real photos
    badly; use smooth structure so JPEG size is realistic-ish)."""
    import numpy as np

    from mxnet_tpu.io.recordio import IndexedRecordIO, IRHeader, pack_img

    rec = IndexedRecordIO(path + ".idx", path + ".rec", "w")
    rs = np.random.RandomState(0)
    yy, xx = np.mgrid[0:hw, 0:hw]
    total_bytes = 0
    for i in range(n_images):
        img = np.stack([
            (yy * (i % 7 + 1) // 4 + rs.randint(0, 32)) % 256,
            (xx // 2 + i * 11) % 256,
            ((xx + yy) // 3 + rs.randint(0, 64)) % 256,
        ], axis=2).astype(np.uint8)
        payload = pack_img(IRHeader(0, float(i % 1000), i, 0), img,
                           quality=quality, img_fmt=".jpg")
        total_bytes += len(payload)
        rec.write_idx(i, payload)
    rec.close()
    return total_bytes


def bench_pipeline(rec_path, n_images, threads, data_shape=(3, 224, 224),
                   batch_size=32, epochs=2):
    """imgs/s through the full ImageRecordIter path (decode->aug->batchify).
    Reports the best of ``epochs`` timed passes (the first pass carries the
    cold-cache cost, so with epochs>=2 the figure is a warmed number)."""
    from mxnet_tpu.io import ImageRecordIter

    it = ImageRecordIter(path_imgrec=rec_path + ".rec",
                         data_shape=data_shape, batch_size=batch_size,
                         resize=max(data_shape[1], data_shape[2]) + 16,
                         shuffle=False,
                         mean_r=123.0, mean_g=117.0, mean_b=104.0,
                         std_r=58.4, std_g=57.1, std_b=57.4,
                         preprocess_threads=threads)
    best = 0.0
    for _ in range(epochs):
        it.reset()
        t0 = time.perf_counter()
        seen = 0
        for batch in it:
            seen += batch.data[0].shape[0]
        dt = time.perf_counter() - t0
        best = max(best, seen / dt)
    it.close()
    return round(best, 1)


def bench_resnet_step_cpu(batch=32, steps=3):
    """ResNet-50 train-step demand (imgs/s) on the CPU backend — the
    pipeline must beat the step's consumption for the M2 gate to hold."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import nd, optimizer
    from mxnet_tpu.gluon.model_zoo.vision import get_model
    from mxnet_tpu.parallel import TrainStep

    import jax

    mx.random.seed(0)
    net = get_model("resnet50_v1", classes=1000)
    net.initialize()
    rs = np.random.RandomState(0)
    x = nd.array(rs.randn(batch, 3, 224, 224).astype("float32"))
    y = nd.array(rs.randint(0, 1000, (batch,)), dtype="int32")
    _ = net(x)

    def loss_fn(out, y):
        import jax.numpy as jnp

        logits = (out._data if hasattr(out, "_data") else out).astype(
            jnp.float32)
        yv = (y._data if hasattr(y, "_data") else y).astype(jnp.int32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, yv[:, None], axis=-1).mean()

    ts = TrainStep(net, loss_fn, optimizer.SGD(learning_rate=0.1),
                   mesh=None, n_model_inputs=1)
    loss = ts(x, y)
    float(np.asarray(jax.device_get(loss)))  # absorb compile
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = ts(x, y)
    float(np.asarray(jax.device_get(loss)))
    dt = (time.perf_counter() - t0) / steps
    return round(batch / dt, 1), round(dt, 3)


def tpu_demand_from_artifact():
    """Measured TPU-side consumption (imgs/s) if a MODELBENCH artifact with
    a resnet50 row exists; None otherwise (pending hardware)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for name in sorted(os.listdir(repo), reverse=True):
        if name.startswith("MODELBENCH") and name.endswith(".json") \
                and "DRYRUN" not in name:
            try:
                rows = json.load(open(os.path.join(repo, name)))
            except (OSError, ValueError):
                continue
            for r in rows if isinstance(rows, list) else [rows]:
                if r.get("metric") == "resnet50_images_per_sec" and \
                        r.get("platform") == "tpu" and r.get("value", 0) > 0:
                    return {"imgs_per_sec": r["value"], "artifact": name}
    return None


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-images", type=int, default=192)
    ap.add_argument("--hw", type=int, default=256)
    ap.add_argument("--threads", default="1,2,4,8")
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--skip-step", action="store_true",
                    help="skip the ResNet-50 CPU step measurement")
    ap.add_argument("--json", default=None)
    args = ap.parse_args()

    # force CPU: this is a HOST pipeline benchmark; never claim the chip
    import jax

    jax.config.update("jax_platforms", "cpu")

    import tempfile

    with tempfile.TemporaryDirectory() as td:
        rec = os.path.join(td, "iobench")
        t0 = time.perf_counter()
        nbytes = make_dataset(rec, args.n_images, args.hw)
        pack_s = time.perf_counter() - t0

        result = {
            "metric": "input_pipeline_imgs_per_sec",
            "n_images": args.n_images,
            "jpeg_hw": args.hw,
            "mean_jpeg_kb": round(nbytes / args.n_images / 1024, 1),
            "pack_s": round(pack_s, 2),
            "decode_path": "native ITU T.81 baseline JPEG (jpeg.cc) + "
                           "runtime.cc resize/crop/batchify",
        }
        per_threads = {}
        for t in [int(x) for x in args.threads.split(",")]:
            per_threads[str(t)] = bench_pipeline(rec, args.n_images, t,
                                                 batch_size=args.batch)
        result["imgs_per_sec_by_threads"] = per_threads
        result["value"] = max(per_threads.values())
        result["unit"] = "img/s"

        if not args.skip_step:
            demand, step_s = bench_resnet_step_cpu(batch=args.batch)
            result["resnet50_cpu_step_imgs_per_sec"] = demand
            result["resnet50_cpu_step_s"] = step_s
            result["pipeline_covers_cpu_step"] = result["value"] >= demand
        tpu = tpu_demand_from_artifact()
        result["resnet50_tpu_demand"] = tpu or "pending hardware"
        if tpu:
            result["pipeline_covers_tpu_step"] = \
                result["value"] >= tpu["imgs_per_sec"]

    print(json.dumps(result), flush=True)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(result, f, indent=1)


if __name__ == "__main__":
    main()
