"""Shared synthetic data for the examples.

The reference examples download MNIST/ImageNet; this environment has no
network egress, so examples train on a *learnable* synthetic stand-in:
each class is a Gaussian blob around a fixed random prototype image, so
losses genuinely decrease and accuracy genuinely rises — the distributed
mechanics being demonstrated are identical.

Every example shards data by rank exactly the way the reference does with
``tf.data.shard`` / ``DistributedSampler`` (examples/pytorch_mnist.py:43-64).
"""

from __future__ import annotations

import numpy as np


def synthetic_mnist(n: int = 4096, num_classes: int = 10, seed: int = 1234,
                    image_shape=(28, 28, 1)):
    """(images [n,*image_shape] float32 in [0,1], labels [n] int32)."""
    rng = np.random.RandomState(seed)
    protos = rng.rand(num_classes, *image_shape).astype(np.float32)
    labels = rng.randint(0, num_classes, size=n).astype(np.int32)
    images = protos[labels] + 0.3 * rng.randn(n, *image_shape).astype(
        np.float32)
    return np.clip(images, 0.0, 1.0), labels


def shard_for_rank(arrays, rank: int, size: int):
    """Contiguous per-rank shard of each array — the DistributedSampler
    pattern (examples/pytorch_mnist.py:43-64)."""
    n = arrays[0].shape[0]
    per = n // size
    sl = slice(rank * per, (rank + 1) * per)
    return tuple(a[sl] for a in arrays)


def torch_image_model(name: str, num_classes: int = 100):
    """torchvision model when available (the reference's PyTorch examples
    use torchvision); otherwise a small in-file conv net so the example
    still runs — returns (model, actual_name) with the fallback clearly
    relabeled so its numbers/checkpoints are never mistaken for the
    requested model's."""
    try:
        import torchvision.models as tvm
        return getattr(tvm, name)(num_classes=num_classes), name
    except ImportError:
        import torch.nn as nn
        model = nn.Sequential(
            nn.Conv2d(3, 32, 3, stride=2, padding=1), nn.ReLU(),
            nn.Conv2d(32, 64, 3, stride=2, padding=1), nn.ReLU(),
            nn.AdaptiveAvgPool2d(1), nn.Flatten(),
            nn.Linear(64, num_classes))
        actual = f"tiny-convnet (torchvision missing; NOT {name})"
        print(f"torchvision not installed: training {actual}")
        return model, actual


def synthetic_imagenet(batch: int, image_size: int = 224, classes: int = 1000,
                       seed: int = 0):
    """Random images/labels for throughput benchmarks (the reference's
    synthetic benchmark uses pure random data,
    examples/tensorflow_synthetic_benchmark.py:60-66)."""
    rng = np.random.RandomState(seed)
    images = rng.rand(batch, image_size, image_size, 3).astype(np.float32)
    labels = rng.randint(0, classes, size=batch).astype(np.int32)
    return images, labels


def text8_like_tokens(n: int = 100_000, vocab: int = 5000, seed: int = 7):
    """Zipf-distributed token stream standing in for the word2vec corpus
    (examples/tensorflow_word2vec.py downloads text8)."""
    rng = np.random.RandomState(seed)
    tokens = rng.zipf(1.3, size=n).astype(np.int64)
    return np.clip(tokens, 0, vocab - 1).astype(np.int32)
