"""An identity MBConv block of EfficientNet-B0 with squeeze-and-excitation
(Tan & Le 2019, Table 1; the stage "MBConv6, k3x3, 24 channels", block
string ``r2_k3_s22_e6_i16_o24_se0.25`` of the authors' TensorFlow
reference, whose second repeat has stride 1 and 24 input filters), with
batch norm folded into weights and biases and ``sigmoid(t) = 1 / (1 +
exp(-t))``, ``swish(t) = t * sigmoid(t)``:

    x  = act_scale * (code - 128)
    h1 = swish(conv1x1(x; cin -> 6 cin) + b1)      zero padding 1
    h2 = swish(dwconv3x3(h1) + b2)
    s  = mean over the image of h2
    g  = sigmoid(we . swish(wr . s + br) + be)     6 cin -> cin / 4 -> 6 cin
    y  = conv1x1(h2 * g; 6 cin -> cin) + b3 + x

``inputs["ifmap"]`` holds uint8 codes ``(cin, h + 2, w + 2)``; the block's
input is ``x`` on the centre ``h x w`` (the one-pixel ring is not part of
it).  The output is ``(cin, h, w)``.  Its one departure from the published
block: the input arrives as uint8 codes with a zero point and a
per-tensor scale, not float32.  The weights are drawn here from the seed,
in the order the served app draws them."""

import numpy as np

EXPAND = 6               # expansion ratio of MBConv6
SE_RATIO = 0.25          # squeeze channels: cin * 0.25
KERNEL = 3               # depthwise kernel
WEIGHT_SEED = 1905       # the configuration's make_app weight_seed
ACT_SCALE = 1 / 32       # ... and act_scale
ZERO_POINT = 128


def weights(cin, seed):
    """``w1 (cin, mid), b1, w2 (3, 3, mid) [ky, kx, channel], b2, wr (mid,
    sq), br, we (sq, mid), be, w3 (mid, cin), b3`` with ``mid = 6 cin``
    and ``sq = max(1, int(cin / 4))``: He-normal weights (std
    ``sqrt(2 / fan_in)``; a depthwise filter's fan-in is 9) and biases of
    standard deviation 0.1, drawn in that order, rounded to float32."""
    mid, sq = cin * EXPAND, max(1, int(cin * SE_RATIO))
    rng = np.random.default_rng(seed)
    out = []
    for shape, fan_in in (((cin, mid), cin), ((mid,), None),
                          ((KERNEL, KERNEL, mid), KERNEL * KERNEL), ((mid,), None),
                          ((mid, sq), mid), ((sq,), None),
                          ((sq, mid), sq), ((mid,), None),
                          ((mid, cin), mid), ((cin,), None)):
        scale = 0.1 if fan_in is None else np.sqrt(2.0 / fan_in)
        out.append((rng.standard_normal(shape) * scale).astype(np.float32))
    return tuple(out)


def reference(inputs, xp=np, dtype=np.float64, *, weight_seed=WEIGHT_SEED,
              act_scale=ACT_SCALE):
    codes = xp.asarray(inputs["ifmap"], dtype)
    cin, rows, cols = codes.shape
    h, w = rows - 2, cols - 2
    w1, b1, w2, b2, wr, br, we, be, w3, b3 = (
        xp.asarray(a, dtype) for a in weights(cin, weight_seed))

    def sigmoid(t):
        return 1 / (1 + xp.exp(-t))

    def swish(t):
        return t * sigmoid(t)

    def channels(wt, v):                        # sum_c wt[c, o] * v[c, ...]
        return xp.tensordot(wt, v, axes=([0], [0]))

    x = (codes[:, 1:h + 1, 1:w + 1] - ZERO_POINT) * act_scale
    h1 = swish(channels(w1, x) + b1[:, None, None])
    h1 = xp.pad(h1, ((0, 0), (1, 1), (1, 1)))   # zero padding 1
    acc = None
    for ky in range(KERNEL):                    # depthwise: one tap at a time
        for kx in range(KERNEL):
            term = h1[:, ky:ky + h, kx:kx + w] * w2[ky, kx][:, None, None]
            acc = term if acc is None else acc + term
    h2 = swish(acc + b2[:, None, None])
    s = xp.sum(h2, axis=(1, 2)) / (h * w)       # squeeze
    g = sigmoid(channels(we, swish(channels(wr, s) + br)) + be)
    return channels(w3, h2 * g[:, None, None]) + b3[:, None, None] + x
