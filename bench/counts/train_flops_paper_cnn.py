"""Training operations of the paper's CNN per sample, from its shapes
(``models/paper_cnn.py``).

Forward and backward of one sample: the 3x3 valid convolution (forward
and weight gradient; the input needs no gradient), the first dense
layer (forward, weight gradient and input gradient) and the second
(the same three).  Multiply-adds count two operations.  Bias adds,
activations, pooling and the optimizer's elementwise update are left
out: they are not matrix work.
"""
import numpy as np

from models import paper_cnn


def per_sample(cfg: dict) -> int:
    s = paper_cnn.cnn_shapes(cfg)
    kh, kw, _, _ = s["conv_w"]
    positions = (cfg["input_hw"] - kh + 1) * (cfg["input_hw"] - kw + 1)
    conv = 2 * positions * int(np.prod(s["conv_w"]))
    dense1 = 2 * int(np.prod(s["dense1_w"]))
    dense2 = 2 * int(np.prod(s["dense2_w"]))
    return 2 * conv + 3 * dense1 + 3 * dense2


def per_round(cfg: dict) -> int:
    """All K users' L local steps on minibatches of ``batch_size``."""
    return per_sample(cfg) * cfg["K"] * cfg["L"] * cfg["batch_size"]
