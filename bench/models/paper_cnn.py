"""The paper's CNN (arXiv 2412.10878, sec. IV): Conv(32, 3x3) + ReLU,
MaxPool 2x2, Dense(64) + ReLU, Dense(n_classes), with its softmax
cross-entropy, on synthetic images of the dataset's shape.

The inputs and the reference's forward pass and loss are written here
and import nothing of the program; ``engine_inputs`` hands the engine
the program's own CNN (``repro.fl.cnn``).
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Dict

import numpy as np

LEAVES = ("conv_b", "conv_w", "dense1_b", "dense1_w", "dense2_b",
          "dense2_w")
# the [K, d] stack of one round's deltas fits: fold all K in one sum
FOLD_BLOCK = None

NOISE_CHUNKS = 16      # fixed, so the images do not depend on the threads
NOISE_THREADS = 4


# ------------------------------------------------------------ inputs
def make_images(n: int, hw: int, channels: int, n_classes: int,
                noise: float, seed: int):
    """Class-conditional low-frequency templates, shifted by up to 3
    pixels per sample, plus Gaussian noise, scaled to [0, 1] — the
    repository's synthetic image task, drawn in bulk: each image is one
    row of a table of every template at every shift, and the noise is
    drawn in fixed chunks, each from its own stream, by a few threads
    (numpy's generators release the GIL while they fill)."""
    rng = np.random.default_rng(seed)
    yy, xx = np.mgrid[0:hw, 0:hw].astype(np.float32) / hw
    f = rng.uniform(1.0, 4.0, (n_classes, 2)).astype(np.float32)
    ph = rng.uniform(0, 2 * np.pi, (n_classes, 2)).astype(np.float32)
    base = (np.sin(2 * np.pi * f[:, 0, None, None] * xx + ph[:, 0, None, None])
            * np.cos(2 * np.pi * f[:, 1, None, None] * yy
                     + ph[:, 1, None, None]))
    gain = rng.uniform(0.3, 1.0, (n_classes, channels)).astype(np.float32)
    templates = base[..., None] * gain[:, None, None, :]   # [C, H, W, ch]
    y = rng.integers(0, n_classes, n)
    shifts = rng.integers(-3, 4, (n, 2))
    # table[c, a + 3, b + 3][r, s] = templates[c, (r - a) % hw, (s - b) % hw]
    table = np.stack([np.stack([np.roll(templates, (a, b), axis=(1, 2))
                                for b in range(-3, 4)], 1)
                      for a in range(-3, 4)], 1)   # [C, 7, 7, H, W, ch]
    x = table[y, shifts[:, 0] + 3, shifts[:, 1] + 3]
    streams = np.random.SeedSequence(seed).spawn(NOISE_CHUNKS)
    cuts = np.linspace(0, n, NOISE_CHUNKS + 1).astype(int)

    def add_noise(i):
        part = x[cuts[i]:cuts[i + 1]]
        z = np.random.default_rng(streams[i]).standard_normal(
            part.shape, dtype=np.float32)
        z *= noise
        part += z

    with ThreadPoolExecutor(NOISE_THREADS) as pool:
        list(pool.map(add_noise, range(NOISE_CHUNKS)))
    x -= x.min()
    x /= x.max() + 1e-9
    return x.astype(np.float32, copy=False), y.astype(np.int64)


def make_data(cfg: dict, seed: int):
    """(images [n, H, W, C] float32, labels [n], labels): the users'
    shards are drawn by label."""
    x, y = make_images(cfg["n_train"] + cfg["n_test"], cfg["input_hw"],
                       cfg["channels"], cfg["n_classes"],
                       cfg["image_noise"], seed)
    return x, y, y


def cnn_shapes(cfg: dict) -> Dict[str, tuple]:
    hw, ch, nf, du, nc = (cfg["input_hw"], cfg["channels"],
                          cfg["conv_filters"], cfg["dense_units"],
                          cfg["n_classes"])
    flat = ((hw - 2) // 2) ** 2 * nf
    return {"conv_w": (3, 3, ch, nf), "conv_b": (nf,),
            "dense1_w": (flat, du), "dense1_b": (du,),
            "dense2_w": (du, nc), "dense2_b": (nc,)}


def init_params(cfg: dict, seed: int):
    """He-style normal weights over fan-in, zero biases, made on the
    device in one jitted call and returned as float32 device arrays."""
    import jax
    import jax.numpy as jnp

    shapes = cnn_shapes(cfg)

    @jax.jit
    def make(key):
        out = {}
        for i, name in enumerate(LEAVES):
            shp = shapes[name]
            if name.endswith("_b"):
                out[name] = jnp.zeros(shp, jnp.float32)
            else:
                fan = int(np.prod(shp[:-1]))
                out[name] = (jax.random.normal(jax.random.fold_in(key, i),
                                               shp, jnp.float32)
                             / np.float32(np.sqrt(fan)))
        return out

    return make(jax.random.PRNGKey(int(seed)))


# ----------------------------------------------------------- program
def engine_inputs(cfg: dict, inputs):
    """The engine's image sets and the program's CNN as a ModelSpec,
    starting from ``inputs.params0``."""
    import jax.numpy as jnp

    from repro.data.synthetic import ImageDataset
    from repro.fl.cnn import cnn_accuracy, cnn_loss
    from repro.fl.models import ModelSpec

    n = inputs.n_train
    train = ImageDataset(x=inputs.x[:n], y=inputs.y[:n],
                         n_classes=cfg["n_classes"])
    test = ImageDataset(x=inputs.x[n:], y=inputs.y[n:],
                        n_classes=cfg["n_classes"])
    params0 = {k: jnp.asarray(v) for k, v in inputs.params0.items()}
    spec = ModelSpec(name=cfg["model"], init=lambda key: params0,
                     loss=cnn_loss, accuracy=cnn_accuracy)
    return train, test, spec


# --------------------------------------------------------- reference
def forward(p, x, mm):
    """The paper's CNN on x [B, H, W, C]: the 3x3 valid convolution as
    a product of the image's patches with the kernel, ReLU, 2x2 max
    pooling, then the two dense layers."""
    import jax.numpy as jnp

    B, H, W, C = x.shape
    kh, kw, _, F = p["conv_w"].shape
    Ho, Wo = H - kh + 1, W - kw + 1
    patches = jnp.concatenate([x[:, i:i + Ho, j:j + Wo, :]
                               for i in range(kh) for j in range(kw)],
                              axis=-1)                  # [B, Ho, Wo, 9C]
    h = mm(patches.reshape(-1, kh * kw * C),
           p["conv_w"].reshape(kh * kw * C, F)).reshape(B, Ho, Wo, F)
    h = jnp.maximum(h + p["conv_b"], 0)
    h = h.reshape(B, Ho // 2, 2, Wo // 2, 2, F).max(axis=(2, 4))
    h = jnp.maximum(mm(h.reshape(B, -1), p["dense1_w"]) + p["dense1_b"], 0)
    return mm(h, p["dense2_w"]) + p["dense2_b"]


def loss(p, x, y, mm):
    import jax
    import jax.numpy as jnp

    logp = jax.nn.log_softmax(forward(p, x, mm))
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))
