"""The Graph Challenge sparse DNN: generator and plain oracle.

Written from the description in Kepner et al., "Sparse Deep Neural Network
Graph Challenge" (HPEC 2019), apart from the program, so that no change to
the program moves the yardstick:

* RadiX-Net radix-32 butterfly layers (Robinett and Kepner, 2018): row
  ``i`` connects to the 32 columns that agree with it outside a 5-bit
  window; layer ``k`` puts the window on the ``k``-th 5-bit digit of the
  row index (modulo the number of digits, the last one set against the top
  bit), so that consecutive layers mix every bit of the index, and every
  weight is ``1/16``.  ``butterfly_cols`` builds one layer for any window;
  the repository's own generator, which the tests hold it against, moves
  the window 3 bits a layer instead;
* the layer ``y = min(max(W x + b, 0), 32)``;
* the oracle: every layer in float32, each row's 32 products summed by a
  batched matmul over the row's sorted columns.

``control`` names a lower-precision control: the same oracle with each
layer's input activations rounded to ``"bfloat16"`` or to
``"float8_e4m3fn"``.
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np


def _bits(neurons: int) -> int:
    bits = int(np.log2(neurons))
    if 2**bits != neurons or bits < 5:
        raise ValueError("Graph Challenge sizes are powers of two, >= 32")
    return bits


def butterfly_cols(neurons: int, offset: int) -> np.ndarray:
    """int32 ``[neurons, 32]``: each row's sorted column ids when the 5-bit
    window sits at bit ``offset``."""
    mask = 31 << offset
    i = np.arange(neurons, dtype=np.int64)[:, None]
    t = np.arange(32, dtype=np.int64)[None, :]
    return np.sort((i & ~mask) | (t << offset), axis=1).astype(np.int32)


def radix_offsets(neurons: int, layers: int) -> List[int]:
    """The window's bit offset at each layer: the index's 5-bit digits in
    turn, ``0, 5, 10, ...``, the last digit ending at the top bit."""
    bits = _bits(neurons)
    digits = [min(5 * d, bits - 5) for d in range(-(-bits // 5))]
    return [digits[k % len(digits)] for k in range(layers)]


def make_net(config: dict) -> List[np.ndarray]:
    """Column ids of every layer; the weights are all ``config["weight"]``."""
    if int(config["nnz_per_row"]) != 32:
        raise ValueError("the radix-32 butterfly has 32 nonzeros per row")
    n = int(config["neurons"])
    return [butterfly_cols(n, o)
            for o in radix_offsets(n, int(config["layers"]))]


def layer_apply(cols: np.ndarray, weight: float, x: np.ndarray,
                bias: float, clip: float) -> np.ndarray:
    n, k = cols.shape
    data = np.full((n, 1, k), weight, np.float32)
    z = np.matmul(data, x[cols.reshape(-1)].reshape(n, k, x.shape[1]))[:, 0]
    return np.minimum(np.maximum(z + np.float32(bias), 0.0),
                      np.float32(clip))


def dense_inference(config: dict, net: List[np.ndarray], x0: np.ndarray,
                    control: Optional[str] = None) -> np.ndarray:
    """The final activations ``[neurons, batch]`` for inputs ``x0``."""
    import ml_dtypes

    x = np.asarray(x0, np.float32)
    for cols in net:
        if control is not None:
            x = x.astype(getattr(ml_dtypes, control)).astype(np.float32)
        x = layer_apply(cols, float(config["weight"]), x,
                        float(config["bias"]),
                        float(config["activation_clip"]))
    return x
