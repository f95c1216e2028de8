"""Hot-pixel clamp, GRBG demosaic, colour matrix and gamma (the paper's
``camera``, Halide's ``camera_pipe``).  The output is indexed
``[y, yi, x, xi]``: pixel ``(2y + yi, 2x + xi)``."""

from functools import reduce

import numpy as np


def reference(inputs, xp=np, dtype=np.float64):
    a = xp.asarray(inputs["raw"], dtype)
    size = (a.shape[0] - 4) // 2
    e = a.shape[0] - 2                          # denoise extent

    def win(v, dy, dx, h, w):
        return v[dy:dy + h, dx:dx + w]

    c = win(a, 1, 1, e, e)
    nbrs = [win(a, 1, 0, e, e), win(a, 1, 2, e, e),
            win(a, 0, 1, e, e), win(a, 2, 1, e, e)]
    lo, hi = reduce(xp.minimum, nbrs), reduce(xp.maximum, nbrs)
    dn = xp.minimum(xp.maximum(c, lo), hi)

    def at(dx, dy):                             # dn[2x + dx, 2y + dy]
        return dn[dy:dy + 2 * size:2, dx:dx + 2 * size:2][:, None, :, None]

    yi = xp.asarray(np.arange(2).reshape(1, 2, 1, 1), dtype)
    xi = xp.asarray(np.arange(2).reshape(1, 1, 1, 2), dtype)

    def phase(px, py):
        tx = xi if px == 1 else 1 - xi
        ty = yi if py == 1 else 1 - yi
        return tx * ty

    g = (phase(0, 0) * at(0, 0) + phase(1, 1) * at(1, 1)
         + (phase(1, 0) + phase(0, 1)) * ((at(0, 0) + at(1, 1)) / 2))
    r = phase(1, 0) * at(1, 0) + (1 - phase(1, 0)) * ((at(1, 0) + at(3, 0)) / 2)
    b = phase(0, 1) * at(0, 1) + (1 - phase(0, 1)) * ((at(0, 1) + at(0, 3)) / 2)
    ccm_r = (r * 14 + g * 2 - b) / 16
    ccm_g = (r * -1 + g * 14 + b * 2) / 16
    ccm_b = (r * 2 - g + b * 14) / 16
    lum = (ccm_r * 5 + ccm_g * 9 + ccm_b * 2) / 16
    return xp.minimum(xp.maximum(lum + lum * lum / 256, 0), 255)
