"""ImageNet ResNet-50 — the flagship throughput model.

Parity: reference model_zoo/imagenet_resnet50/imagenet_resnet50.py (Keras
builtin ResNet50 over JPEG-encoded records). Here the shared flax ResNet-50
(resnet50_subclass/resnet50_model.py) is instantiated with 1000 classes and
bfloat16 compute — the MXU-native dtype — while parameters stay float32.
No benchmark cell trains it: its rate is not measured on the chip.
"""

import jax.numpy as jnp
import numpy as np
import optax

from elasticdl_tpu.common.constants import Mode
from elasticdl_tpu.data.example import decode_example

try:
    from resnet50_subclass.resnet50_model import ResNet50
except ImportError:
    from model_zoo.resnet50_subclass.resnet50_model import ResNet50


def custom_model(num_classes=1000, dtype="bfloat16"):
    return ResNet50(num_classes=num_classes, dtype=jnp.dtype(dtype))


def loss(output, labels):
    labels = labels.reshape(-1)
    probs = jnp.clip(output, 1e-7, 1.0)
    nll = -jnp.log(
        jnp.take_along_axis(probs, labels[:, None], axis=1)[:, 0]
    )
    return nll.mean()


def optimizer(lr=0.02, momentum=0.9):
    return optax.sgd(lr, momentum=momentum)


def dataset_fn(dataset, mode, _):
    def _parse_data(record):
        r = decode_example(record)
        # keep uint8: the model normalizes on device, so the host->device
        # transfer (often the E2E bottleneck) carries 1 byte/pixel not 4
        features = {"image": r["image"]}
        if mode == Mode.PREDICTION:
            return features
        return features, (r["label"].astype(np.int32) - 1).reshape(-1)

    # image decode is the CPU-heavy stage of this pipeline: run it on
    # the ordered parallel decode pool (in-order merge, so the stream
    # stays deterministic; docs/input_pipeline.md)
    dataset = dataset.map(_parse_data, num_parallel_calls=4)
    if mode == Mode.TRAINING:
        dataset = dataset.shuffle(buffer_size=1024)
    return dataset


def eval_metrics_fn():
    return {
        "accuracy": lambda labels, predictions: np.equal(
            np.argmax(predictions, axis=1).astype(np.int32),
            np.asarray(labels).reshape(-1).astype(np.int32),
        )
    }
