"""3x3 [1 2 1] x [1 2 1] blur over 16 (the paper's ``gaussian``)."""

import numpy as np

WEIGHTS = ((1, 2, 1), (2, 4, 2), (1, 2, 1))


def reference(inputs, xp=np, dtype=np.float64):
    a = xp.asarray(inputs["input"], dtype)
    h, w = a.shape[0] - 2, a.shape[1] - 2
    acc = None
    for dy in range(3):
        for dx in range(3):
            term = a[dy:dy + h, dx:dx + w] * WEIGHTS[dy][dx]
            acc = term if acc is None else acc + term
    return acc / 16
