"""The paper CNN on procedural MNIST-like images (the contract is in
``families/__init__.py``).

* The training set is the program's generator, vectorized: one draw of
  all the pixel noise is the same stream as one draw per image.
* A client's minibatches are index rows into the training set: its shard
  visited in a seeded random order, ``local_batches_per_step`` batches of
  ``batch_size`` per local iteration.
* The model: 2 SAME 5x5 convolutions, each followed by ReLU and a 2x2
  max-pool, fc with ReLU, the class head and log-softmax; He-normal
  weights, zero biases; the loss is the mean negative log-likelihood.

The ReLU and max-pool decisions turn any rounding other than the
program's into a diverging trajectory: the reference at the next lower
precision, in the program's place, ends about 1e-3 away, while a sound
run matches it bit for bit.  No leaf's change is small enough for the
check to leave it out.
"""
from __future__ import annotations

from typing import Any, Dict

import numpy as np

IMAGE_SIZE = 28
NUM_CLASSES = 10
# how many images the generator renders at a time (bounds host memory)
_RENDER_CHUNK = 8192


# ---------------------------------------------------------------------------
# Data
# ---------------------------------------------------------------------------
def _smooth(img: np.ndarray, iters: int) -> np.ndarray:
    for _ in range(iters):
        img = (img
               + np.roll(img, 1, 0) + np.roll(img, -1, 0)
               + np.roll(img, 1, 1) + np.roll(img, -1, 1)) / 5.0
    return img


def _class_template(cls: int, variant: str) -> np.ndarray:
    rng = np.random.default_rng(1000 + cls)
    img = rng.normal(0, 1, (IMAGE_SIZE, IMAGE_SIZE))
    img = _smooth(img, 3 if variant == "digits" else 6)
    q = np.quantile(img, 0.72)
    img = np.where(img > q, 1.0, 0.0)
    img = _smooth(img, 1)
    return img.astype(np.float32)


def _render(templates, labels, rng, noise) -> np.ndarray:
    n = len(labels)
    shifts = rng.integers(-3, 4, size=(n, 2))
    scales = rng.uniform(0.7, 1.3, size=n)
    out = np.empty((n, IMAGE_SIZE, IMAGE_SIZE, 1), np.float32)
    ar = np.arange(IMAGE_SIZE)
    for a in range(0, n, _RENDER_CHUNK):
        b = min(n, a + _RENDER_CHUNK)
        # np.roll by s: out[i] = in[(i - s) mod 28], per image and axis
        r = (ar[None, :] - shifts[a:b, 0:1]) % IMAGE_SIZE
        c = (ar[None, :] - shifts[a:b, 1:2]) % IMAGE_SIZE
        t = templates[labels[a:b]]
        img = t[np.arange(b - a)[:, None, None], r[:, :, None],
                c[:, None, :]]
        img = (img * scales[a:b, None, None]
               + rng.normal(0, noise, (b - a, IMAGE_SIZE, IMAGE_SIZE)))
        out[a:b, :, :, 0] = img
    return np.clip(out, 0.0, 1.5)


def training_set(variant: str, train_n: int, test_n: int, seed: int):
    """(images (N, 28, 28, 1) f32, labels (N,) int32) of the training
    split; the test labels are drawn (and dropped) to keep the stream."""
    if variant not in ("digits", "fashion"):
        raise ValueError(f"unknown data variant {variant!r}")
    templates = np.stack([_class_template(c, variant)
                          for c in range(NUM_CLASSES)])
    noise = 0.20 if variant == "digits" else 0.30
    rng = np.random.default_rng(seed + (0 if variant == "digits" else 77))
    train_y = rng.integers(0, NUM_CLASSES, train_n).astype(np.int32)
    rng.integers(0, NUM_CLASSES, test_n)
    return _render(templates, train_y, rng, noise), train_y


def batch_indices(indices: np.ndarray, cid: int, batch_size: int,
                  num_batches: int, seed: int) -> np.ndarray:
    """(num_batches, batch_size) indices into the training set: the
    client's shard visited in a seeded random order, reshuffled when an
    epoch runs out."""
    rng = np.random.default_rng((seed * 9176 + cid) % (2 ** 63))
    n = len(indices)
    order = rng.permutation(n)
    ptr = 0
    rows = []
    for _ in range(num_batches):
        if ptr + batch_size > n:
            order = rng.permutation(n)
            ptr = 0
        rows.append(order[ptr:ptr + batch_size])
        ptr += batch_size
    return indices[np.stack(rows)].astype(np.int32)


def dataset(config: Dict[str, Any], seed: int):
    """((images, labels), labels): the labels are the partitions' keys."""
    t = config["task"]
    x, y = training_set(t["variant"], t["train_n"], t["test_n"], seed)
    return (x, y), y


def local_steps(config: Dict[str, Any], k: int) -> int:
    return k * config["task"]["local_batches_per_step"]


def batches(config: Dict[str, Any], shard: np.ndarray, cid: int, k: int,
            seed: int) -> np.ndarray:
    return batch_indices(shard, cid, config["task"]["batch_size"],
                         local_steps(config, k), seed)


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------
def init_params(model: Dict[str, int], seed: int):
    """He-normal paper CNN from ``jax.random.PRNGKey(seed)``; biases 0."""
    import jax
    import jax.numpy as jnp

    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    ksz, c1, c2 = model["kernel"], model["conv1"], model["conv2"]
    flat = (model["image_size"] // 4) ** 2 * c2

    def he(kk, shape, fan_in):
        return (jax.random.normal(kk, shape)
                * jnp.sqrt(2.0 / fan_in)).astype(jnp.float32)

    return {
        "conv1_w": he(k[0], (ksz, ksz, model["channels"], c1),
                      ksz * ksz * model["channels"]),
        "conv1_b": jnp.zeros((c1,), jnp.float32),
        "conv2_w": he(k[1], (ksz, ksz, c1, c2), ksz * ksz * c1),
        "conv2_b": jnp.zeros((c2,), jnp.float32),
        "fc1_w": he(k[2], (flat, model["fc"]), flat),
        "fc1_b": jnp.zeros((model["fc"],), jnp.float32),
        "fc2_w": he(k[3], (model["fc"], model["num_classes"]), model["fc"]),
        "fc2_b": jnp.zeros((model["num_classes"],), jnp.float32),
    }


def _forward(p, x):
    import jax

    def conv(x, w, b):
        return jax.lax.conv_general_dilated(
            x, w, (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC")) + b

    def pool(x):
        return jax.lax.reduce_window(x, -np.inf, jax.lax.max,
                                     (1, 2, 2, 1), (1, 2, 2, 1), "VALID")

    x = pool(jax.nn.relu(conv(x, p["conv1_w"], p["conv1_b"])))
    x = pool(jax.nn.relu(conv(x, p["conv2_w"], p["conv2_b"])))
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(x @ p["fc1_w"] + p["fc1_b"])
    return jax.nn.log_softmax(x @ p["fc2_w"] + p["fc2_b"], axis=-1)


def loss(p, batch, data):
    """Mean NLL of the images and labels at the index row ``batch``."""
    import jax.numpy as jnp

    x, y = data
    images, labels = x[batch], y[batch]
    logp = _forward(p, images)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


# ---------------------------------------------------------------------------
# Counts
# ---------------------------------------------------------------------------
def param_count(model: Dict[str, int]) -> int:
    """Two SAME 5x5 convs, two 2x2 pools, fc and the class head, with
    biases."""
    k, ch, c1, c2 = (model["kernel"], model["channels"], model["conv1"],
                     model["conv2"])
    flat = (model["image_size"] // 4) ** 2 * c2
    return (k * k * ch * c1 + c1 + k * k * c1 * c2 + c2
            + flat * model["fc"] + model["fc"]
            + model["fc"] * model["num_classes"] + model["num_classes"])


def forward_flops_per_sample(model: Dict[str, int]) -> int:
    """2 x the multiply-adds of one image's forward pass, counted over
    every output position of the SAME convolutions (the zero border
    included: the chip computes it)."""
    s, k, ch, c1, c2 = (model["image_size"], model["kernel"],
                        model["channels"], model["conv1"], model["conv2"])
    macs = (s * s * c1 * k * k * ch
            + (s // 2) ** 2 * c2 * k * k * c1
            + (s // 4) ** 2 * c2 * model["fc"]
            + model["fc"] * model["num_classes"])
    return 2 * macs


# ---------------------------------------------------------------------------
# Program side
# ---------------------------------------------------------------------------
def task(config: Dict[str, Any], iid: bool, seed: int):
    """``repro.core.tasks.CNNTask`` at the configuration's widths."""
    from repro.configs.paper_cnn import CNNConfig
    from repro.core.tasks import CNNTask

    t, m = config["task"], config["model"]
    cnn = CNNConfig(**{k: m[k] for k in (
        "image_size", "channels", "conv1", "conv2", "kernel", "fc",
        "num_classes")})
    return CNNTask(
        variant=t["variant"], iid=iid,
        num_clients=t["num_clients"], train_n=t["train_n"],
        test_n=t["test_n"], batch_size=t["batch_size"], lr=t["lr"],
        local_batches_per_step=t["local_batches_per_step"],
        cnn_cfg=cnn, seed=seed)


def samples_per_iteration(config: Dict[str, Any]) -> int:
    t = config["task"]
    return t["local_batches_per_step"] * t["batch_size"]
