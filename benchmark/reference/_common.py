"""Plain reference pieces shared by the flow configurations.

Straightforward `jax.numpy`/`lax` in float32 with matmul precision
`highest`: convolutions, the FlowNet-style decoder, the unsupervised
pyramid loss (LRN, bilinear warp with independently clipped neighbours,
generalized Charbonnier, first-order smoothness) and Adam. Nothing here
imports the program, and nothing takes a value the program made: weights
come from `make_params` (seeded), inputs from the harness's generator.

The hooks are two. `Params.q`: a function applied to every convolution's
input and kernel; `None` for the reference, a quantiser for the control
that decides whether the comparison can see a lower precision.
`Params.through`: for each named part a factor on the gradient that flows
BACK through it (its operands pass through `Params.backward_scaled`, values
unchanged); `make_trainer(cuts=...)` hands 1.0 and, for the second gradient
it takes at the first step, 0.0, as an argument of the one compiled
program, and the comparison learns from the two what part of the gradient
flows back through that part.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST


# --------------------------------------------------------------- parameters


class Params:
    """Flat store of parameters, keyed by the layer's path. Built with
    `values`, it hands out what it was given and checks the shape; built
    with `spec`, a list, it records (path, shape, kind) of every parameter
    a forward pass asks for and hands out zeros."""

    def __init__(self, values=None, q=None, spec=None, through=None):
        self.values = {} if values is None else values
        self.q = q
        self.spec = spec
        self.through = through or {}

    def get(self, path: str, shape: tuple, kind: str):
        if self.spec is not None:
            self.spec.append((path, tuple(shape), kind))
            return jnp.zeros(shape, jnp.float32)
        v = self.values[path]
        assert tuple(v.shape) == tuple(shape), (path, v.shape, shape)
        return v

    def quant(self, x):
        return x if self.q is None else self.q(x)

    def backward_scaled(self, part: str, *xs):
        """The operands of `part`, their values as they are; where
        `through` names the part, the gradient that flows back into them is
        multiplied by its factor (x + 0 forward, factor * cotangent back)."""
        if part not in self.through:
            return xs
        held = [lax.stop_gradient(x) for x in xs]
        return tuple(h + self.through[part] * (x - h) for x, h in zip(xs, held))


def bilinear_kernel(shape):
    """(kh, kw, cin, cout) bilinear upsampling, identity across channels."""
    kh, kw, cin, cout = shape

    def axis(k):
        f = int(np.ceil(k / 2.0))
        c = (2 * f - 1 - f % 2) / (2.0 * f)
        return 1 - np.abs(np.arange(k) / f - c)

    up = jnp.asarray(np.outer(axis(kh), axis(kw)), jnp.float32)
    return up[:, :, None, None] * jnp.eye(cin, cout, dtype=jnp.float32)


def param_spec(forward, example) -> list:
    """[(path, shape, kind)] of every parameter `forward` asks for, found
    by tracing it abstractly: nothing is computed or compiled."""
    spec: list = []
    jax.eval_shape(lambda x: forward(Params(spec=spec), x), example)
    return spec


def make_params(forward, example, base_key, seed_key, jitter: float) -> dict:
    """All parameters of `forward` in one jitted call: the published
    initialisation (glorot-uniform kernels, bilinear transposed kernels,
    zero biases). Every kernel is lim * (b + jitter * s), b uniform in
    (-1, 1) from `base_key`, which the configuration fixes, and s from
    `seed_key`: the seed moves every weight, and leaves the flow field and
    so the work of the loss's data-dependent gather all but alone (PERF.md,
    "the seed and the gather"). Two draws, sliced into the leaves."""
    spec = param_spec(forward, example)
    total = sum(math.prod(shape) for _, shape, kind in spec if kind == "glorot")

    def init(kb, ks):
        draw = (jax.random.uniform(kb, (total,), jnp.float32, -1.0, 1.0)
                + jitter * jax.random.uniform(ks, (total,), jnp.float32, -1.0, 1.0))
        values, at = {}, 0
        for path, shape, kind in spec:
            if kind == "glorot":
                kh, kw, cin, cout = shape
                n = math.prod(shape)
                lim = math.sqrt(6.0 / (kh * kw * cin + kh * kw * cout))
                values[path] = lim * draw[at:at + n].reshape(shape)
                at += n
            elif kind == "bilinear":
                values[path] = bilinear_kernel(shape)
            elif kind == "zeros":
                values[path] = jnp.zeros(shape, jnp.float32)
            else:
                raise ValueError(kind)
        return values

    return jax.jit(init)(base_key, seed_key)


# ------------------------------------------------------------------- layers

_DN = ("NHWC", "HWIO", "NHWC")


def conv(p: Params, path: str, x, cout: int, kernel=(3, 3), stride: int = 1,
         act=None, sub: str = "Conv_0"):
    """SAME convolution + bias, then `act`."""
    kh, kw = kernel
    w = p.get(f"{path}/{sub}/kernel", (kh, kw, x.shape[-1], cout), "glorot")
    b = p.get(f"{path}/{sub}/bias", (cout,), "zeros")
    y = lax.conv_general_dilated(p.quant(x), p.quant(w), (stride, stride),
                                 "SAME", dimension_numbers=_DN,
                                 precision=HIGHEST)
    y = y + b
    return y if act is None else act(y)


def deconv(p: Params, path: str, x, cout: int, scale: int = 2, act=None):
    """Transposed convolution, kernel 2*scale, stride scale, SAME: the input
    dilated by the stride, padded, and correlated with the kernel."""
    k = 2 * scale
    w = p.get(f"{path}/ConvTranspose_0/kernel", (k, k, x.shape[-1], cout),
              "bilinear")
    b = p.get(f"{path}/ConvTranspose_0/bias", (cout,), "zeros")
    pad_len = k + scale - 2
    pad_a = k - 1 if scale > k - 1 else int(math.ceil(pad_len / 2))
    pad = (pad_a, pad_len - pad_a)
    y = lax.conv_general_dilated(p.quant(x), p.quant(w), (1, 1), (pad, pad),
                                 lhs_dilation=(scale, scale),
                                 dimension_numbers=_DN, precision=HIGHEST)
    y = y + b
    return y if act is None else act(y)


def relu(x):
    # written with `where`, so that the derivative at exactly 0 is 0 (a
    # `maximum` would split it between its two arguments)
    return jnp.where(x > 0, x, 0.0)


def elu(x):
    # derivative 1 at exactly 0, from both sides
    return jnp.where(x > 0, x, jnp.expm1(jnp.where(x > 0, 0.0, x)))


def max_pool3(x, stride: int = 2):
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1),
                             (1, stride, stride, 1), "SAME")


def avg_pool3(x):
    """3x3 stride-1 SAME mean, the zero padding counted."""
    s = lax.reduce_window(x, 0.0, lax.add, (1, 3, 3, 1), (1, 1, 1, 1), "SAME")
    return s / 9.0


def flow_decoder(p: Params, feats, widths, scales, path: str = "decoder"):
    """`feats` coarsest first. At each level a linear 3x3 head predicts the
    flow; features and flow are upsampled by transposed convolutions (ELU
    on the features, none on the flow), cropped to the skip and
    concatenated with it. Returns the flows finest first."""
    n = len(feats)
    flows = []
    feat = feats[0]
    for k in range(n - 1):
        pr = conv(p, f"{path}/pr{n - k}", feat, 2)
        flows.append(pr)
        up_feat = deconv(p, f"{path}/upconv{n - k - 1}", feat, widths[k],
                         scales[k], act=elu)
        up_pr = deconv(p, f"{path}/up_pr{n - k}to{n - k - 1}", pr, 2, scales[k])
        skip = feats[k + 1]
        sh, sw = skip.shape[1:3]
        feat = jnp.concatenate([skip, up_feat[:, :sh, :sw], up_pr[:, :sh, :sw]],
                               axis=-1)
    flows.append(conv(p, f"{path}/pr1", feat, 2))
    return flows[::-1]


# --------------------------------------------------------------------- loss


def preprocess(images, mean):
    return (images - jnp.asarray(mean, jnp.float32)) / 255.0


def lrn(x, beta: float = 0.7):
    """Across-channel LRN, radius 4 over 3 channels: one shared denominator."""
    return x / jnp.power(1.0 + jnp.sum(jnp.square(x), -1, keepdims=True), beta)


def resize(img, h: int, w: int):
    if img.shape[1] == h and img.shape[2] == w:
        return img
    return jax.image.resize(img, (img.shape[0], h, w, img.shape[3]), "bilinear")


def backward_warp(image, flow):
    """Bilinear sample of `image` at p + flow(p); each of the four
    neighbours is clipped to the border on its own."""
    b, h, w, c = image.shape
    f = flow.reshape(b, h * w, 2)
    fl = jnp.floor(f)
    frac = f - fl
    ys, xs = jnp.meshgrid(jnp.arange(h), jnp.arange(w), indexing="ij")
    x = xs.reshape(-1)[None] + fl[..., 0].astype(jnp.int32)
    y = ys.reshape(-1)[None] + fl[..., 1].astype(jnp.int32)
    x0, x1 = jnp.clip(x, 0, w - 1), jnp.clip(x + 1, 0, w - 1)
    y0, y1 = jnp.clip(y, 0, h - 1), jnp.clip(y + 1, 0, h - 1)
    flat = image.reshape(b, h * w, c)

    def at(yy, xx):
        return jnp.take_along_axis(flat, (yy * w + xx)[..., None], axis=1)

    wx, wy = frac[..., 0:1], frac[..., 1:2]
    out = (at(y0, x0) * (1 - wx) * (1 - wy) + at(y1, x0) * (1 - wx) * wy
           + at(y0, x1) * wx * (1 - wy) + at(y1, x1) * wx * wy)
    return out.reshape(b, h, w, c)


def charbonnier(x, eps: float, alpha: float):
    return jnp.power(jnp.square(x) + eps * eps, alpha)


def level_loss(flow, inputs, outputs, flow_scale: float, hp: dict):
    """(photometric + smoothness loss of one pyramid level, its smoothness
    part alone)."""
    b, h, w, c = inputs.shape
    scaled = flow * flow_scale
    recon = backward_warp(outputs, scaled)
    bw = int(math.ceil(h * hp["border_ratio"]))
    mask = np.zeros((h, w), np.float32)
    mask[bw:h - bw, bw:w - bw] = 1.0
    n_interior = float(mask.sum())
    num_valid = max(b * c * n_interior, 1.0)
    eps = hp["epsilon"]
    photo = jnp.sum(charbonnier(255.0 * (recon - inputs), eps, hp["alpha_c"])
                    * mask[None, :, :, None]) / num_valid
    u, v = scaled[..., 0], scaled[..., 1]
    du = u - jnp.pad(u[:, :, 1:], ((0, 0), (0, 0), (0, 1)))
    dv = v - jnp.pad(v[:, 1:, :], ((0, 0), (0, 1), (0, 0)))
    du = du.at[:, :, -1].set(0.0)
    dv = dv.at[:, -1, :].set(0.0)
    on = 1.0 if n_interior > 0 else 0.0
    smooth = on * (jnp.sum(charbonnier(du, eps, hp["alpha_s"]))
                   + jnp.sum(charbonnier(dv, eps, hp["alpha_s"]))) / num_valid
    return photo + hp["lambda_smooth"] * smooth, smooth


def pyramid_loss(flows, flow_scales, src, tgt, hp: dict):
    """Weighted sum over the levels, finest first, and [2, levels]: the
    levels' own (unweighted) losses and their smoothness parts. `src`/`tgt`
    are raw BGR images; the LRN copies are resized to every level."""
    li = lrn(preprocess(src, hp["mean"]))
    lo = lrn(preprocess(tgt, hp["mean"]))
    total, levels = 0.0, []
    weights = hp["loss_weights"]
    for k, (flow, scale) in enumerate(zip(flows, flow_scales)):
        h, w = flow.shape[1:3]
        weight = weights[k] if k < len(weights) else weights[-1]
        levels.append(level_loss(flow, resize(li, h, w), resize(lo, h, w),
                                 scale, hp))
        total = total + weight * levels[-1][0]
    return total, jnp.stack([jnp.stack(part) for part in zip(*levels)])


def model_loss(forward, flow_scales, values, src, tgt, hp, q=None,
               through=None):
    pair = jnp.concatenate([preprocess(src, hp["mean"]),
                            preprocess(tgt, hp["mean"])], axis=-1)
    flows = forward(Params(values=values, q=q, through=through), pair)
    return pyramid_loss(flows, flow_scales, src, tgt, hp)


# ----------------------------------------------------------------- training


def leaf_norms(tree: dict) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


def make_trainer(forward, flow_scales, hp: dict, block: int, q=None,
                 rows=None, cuts=(), keep_grads: bool = False, watch=()):
    """Returns `steps(values, batches) -> readings`: three (or len(batches))
    Adam steps in float32, gradients accumulated over blocks of `block`
    rows so that the full batch fits beside nothing else.

    `rows`: only these many leading rows of each batch are used, the mean
    taken over them: the planted fault "half of the batch left out".
    `cuts`: for each named part (`Params.through`) the first step's gradient
    is taken a second time with that part's backward cut, by the same
    compiled program; `grad_cuts[name]`
    holds, by leaf, the part of the first gradient that flows back through
    it (`flow_through`).
    `keep_grads`: the whole first gradient on the host (`first_grads`), for
    the calibration's control and faults, which stand in the program's
    place. `watch`: leaves whose value after every step is kept
    (`watched`), for the calibration's look at one leaf.
    """

    @jax.jit
    def block_grad(values, src, tgt, through):
        return jax.value_and_grad(
            lambda v: model_loss(forward, flow_scales, v, src, tgt, hp, q, through),
            has_aux=True)(values)

    whole = {name: jnp.float32(1.0) for name in cuts}

    @jax.jit
    def adam(values, m, v, g, t):
        b1, b2, eps, lr = hp["beta1"], hp["beta2"], hp["adam_eps"], hp["learning_rate"]
        out_p, out_m, out_v = {}, {}, {}
        for k in values:
            out_m[k] = b1 * m[k] + (1 - b1) * g[k]
            out_v[k] = b2 * v[k] + (1 - b2) * jnp.square(g[k])
            mh = out_m[k] / (1 - b1 ** t)
            vh = out_v[k] / (1 - b2 ** t)
            out_p[k] = values[k] - lr * mh / (jnp.sqrt(vh) + eps)
        return out_p, out_m, out_v

    @jax.jit
    def accumulate(acc, g, wgt):
        return {k: acc[k] + wgt * g[k] for k in acc}

    norms = jax.jit(leaf_norms)
    diff_norms = jax.jit(lambda a, b: leaf_norms({k: a[k] - b[k] for k in a}))

    def blocks_of(src, tgt, n):
        for i in range(0, n, block):
            yield jnp.asarray(src[i:i + block]), jnp.asarray(tgt[i:i + block])

    def steps(values0: dict, batches: list) -> dict:
        import time

        t_start = time.perf_counter()
        first_block_s = None
        values = values0
        m = {k: jnp.zeros_like(x) for k, x in values.items()}
        v = {k: jnp.zeros_like(x) for k, x in values.items()}
        losses, level_losses, level_smooth, grad_norms = [], [], [], None
        out = {"watched": {k: [np.asarray(values[k])] for k in watch}}
        for t, (src, tgt) in enumerate(batches, start=1):
            n = src.shape[0] if rows is None else rows
            assert n % block == 0, (n, block)
            g = {k: jnp.zeros_like(x) for k, x in values.items()}
            loss = levels = 0.0
            for sb, tb in blocks_of(src, tgt, n):
                (lb, lv), gb = block_grad(values, sb, tb, whole)
                g = accumulate(g, gb, block / n)
                loss = loss + lb * (block / n)
                levels = levels + lv * (block / n)
                if first_block_s is None:
                    float(lb)
                    first_block_s = time.perf_counter() - t_start
            losses.append(float(loss))
            level_losses.append([float(x) for x in levels[0]])
            level_smooth.append([float(x) for x in levels[1]])
            if t == 1:
                grad_norms = {k: float(x) for k, x in norms(g).items()}
                if keep_grads:
                    out["first_grads"] = {k: np.asarray(x) for k, x in g.items()}
                for name in cuts:
                    gc = {k: jnp.zeros_like(x) for k, x in values.items()}
                    without = dict(whole, **{name: jnp.float32(0.0)})
                    for sb, tb in blocks_of(src, tgt, n):
                        gc = accumulate(gc, block_grad(values, sb, tb, without)[1],
                                        block / n)
                    out.setdefault("grad_cuts", {})[name] = flow_through(g, gc)
            values, m, v = adam(values, m, v, g, float(t))
            for k in watch:
                out["watched"][k].append(np.asarray(values[k]))
        dparam = {k: float(x) for k, x in diff_norms(values, values0).items()}
        out.update({"losses": losses, "level_losses": level_losses,
                    "level_smooth_losses": level_smooth,
                    "grad_norms": grad_norms, "dparam_norms": dparam,
                    "timing": {"first_block_s": first_block_s}})
        return out

    return steps


def flow_through(g: dict, g_cut: dict, reach: float = 1e-3) -> dict:
    """By leaf (float64, on the host), for the leaves the cut part reaches
    (|g - g_cut| over a thousandth of |g|: elsewhere the two differ by the
    compiler's order of sums alone): `d`, of g - g_cut the part that stands
    at a right angle to the leaf's g_cut (a gradient that is a little too
    long or too short all along, as a lower precision or a changed mean
    leaves it, has no share in that part, and a gradient that lacks g -
    g_cut lacks all of it); `dd` = <d, d>; `ref_dot` = <g, d>; `w` = 1 /
    <g, g>, the weight the comparison gives the leaf: a leaf's rounding is
    in proportion to its whole gradient, the part that flows through is of
    one size in every leaf it reaches."""
    size = jax.jit(lambda a, b: {k: (jnp.sum(jnp.square(a[k] - b[k])),
                                     jnp.sum(jnp.square(a[k]))) for k in a})(g, g_cut)
    out = {"d": {}, "dd": {}, "ref_dot": {}, "w": {}}
    for k, (part2, whole2) in size.items():
        if not float(part2) > reach * reach * float(whole2):
            continue
        full, rest = np.asarray(g[k], np.float64), np.asarray(g_cut[k], np.float64)
        part = full - rest
        if np.any(rest):
            part = part - (np.vdot(part, rest) / np.vdot(rest, rest)) * rest
        out["d"][k] = part
        out["dd"][k] = float(np.vdot(part, part))
        out["ref_dot"][k] = float(np.vdot(full, part))
        out["w"][k] = 1.0 / float(np.vdot(full, full))
    return out


# ------------------------------------------------------------------ control


def _straight_through(x, rounded):
    """The rounded value forward, the identity backward: a cast's own
    derivative would round the cotangent to the low precision too, and a
    float8 cotangent underflows to nought."""
    return x + lax.stop_gradient(rounded - x)


def fp8_quantiser(x):
    """Round to float8 e4m3 with one scale per tensor (amax to 448): the
    nearest precision below the bfloat16 the configuration computes in."""
    amax = lax.stop_gradient(jnp.max(jnp.abs(x)))
    s = jnp.where(amax > 0, amax / 448.0, 1.0)
    return _straight_through(
        x, (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s)


def bf16_quantiser(x):
    """Round to bfloat16: the nearest precision below float32."""
    return _straight_through(x, x.astype(jnp.bfloat16).astype(jnp.float32))
