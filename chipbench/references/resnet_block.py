"""The identity bottleneck block of ResNet-50 v1.5, stage conv2_x (He et
al. 2015, Table 1), with batch norm folded into weights and biases:

    h1 = relu(conv1x1(x) + b1); h2 = relu(conv3x3(h1, zero padding 1) + b2)
    y  = relu(conv1x1(h2) + b3 + x)

``inputs["ifmap"]`` holds uint8 activation codes ``(cin, h + 2, w + 2)``:
the activation is ``x = act_scale * code`` on the centre ``h x w`` (the
one-pixel ring is not part of the block's input).  The output is
``(cin, h, w)``.  Its one departure from the published block: the input
arrives as uint8 codes with a per-tensor scale, not float32.  The weights
are drawn here from the seed, in the order the served app draws them."""

import numpy as np

MID = 64                 # bottleneck width of conv2_x
WEIGHT_SEED = 1512       # the configuration's make_app weight_seed
ACT_SCALE = 1 / 64       # ... and act_scale


def weights(cin, mid, seed):
    """``w1 (cin, mid), b1, w2 (3, 3, mid, mid) [ky, kx, in, out], b2,
    w3 (mid, cin), b3``: He-normal weights and biases of standard
    deviation 0.1, drawn in that order, rounded to float32."""
    rng = np.random.default_rng(seed)
    out = []
    for shape, fan_in in (((cin, mid), cin), ((mid,), None),
                          ((3, 3, mid, mid), 9 * mid), ((mid,), None),
                          ((mid, cin), mid), ((cin,), None)):
        scale = 0.1 if fan_in is None else np.sqrt(2.0 / fan_in)
        out.append((rng.standard_normal(shape) * scale).astype(np.float32))
    return tuple(out)


def reference(inputs, xp=np, dtype=np.float64, *, mid=MID,
              weight_seed=WEIGHT_SEED, act_scale=ACT_SCALE):
    codes = xp.asarray(inputs["ifmap"], dtype)
    cin, rows, cols = codes.shape
    h, w = rows - 2, cols - 2
    w1, b1, w2, b2, w3, b3 = (xp.asarray(a, dtype)
                              for a in weights(cin, mid, weight_seed))
    x = codes[:, 1:h + 1, 1:w + 1] * act_scale

    def channels(wt, v):                        # sum_c wt[c, o] * v[c, ...]
        return xp.tensordot(wt, v, axes=([0], [0]))

    h1 = xp.maximum(channels(w1, x) + b1[:, None, None], 0)
    h1 = xp.pad(h1, ((0, 0), (1, 1), (1, 1)))   # zero padding 1
    acc = None
    for ky in range(3):
        for kx in range(3):
            term = channels(w2[ky, kx], h1[:, ky:ky + h, kx:kx + w])
            acc = term if acc is None else acc + term
    h2 = xp.maximum(acc + b2[:, None, None], 0)
    return xp.maximum(channels(w3, h2) + b3[:, None, None] + x, 0)
