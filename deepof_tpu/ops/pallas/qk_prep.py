"""The `fused` preparation pass of `ops/attention.py`'s route: one pair of
Mosaic kernels, `qk_prep_fwd` / `qk_prep_bwd`, between an attention
layer's projection and its attention kernels (`models/lm/layers.py`: the
scopes `mla_proj`, `gqa_proj`).

  y[b, n, s, :] = cast(rotate(norm(x[b, s, n*d:(n+1)*d])))

It takes a product's output as the product leaves it (x[b, s, heads * d],
the float32 accumulations) and writes the attention kernels' operand
(y[b, heads, s, d], head-major, the compute dtype): each tensor is read
once and written once where XLA's elementwise chain (norm, slices, the
rotation's gathers, a pad, a cast and the layout copies) crossed HBM five
times.

  - norm (where a `scale` is given): per head, `x * rsqrt(mean(x^2) + eps)
    * scale`, the statistic in float32 over the head's d channels;
  - rotate: `x * cos + partner(x) * sin` with the angles float32, computed
    OUTSIDE from the positions (`rotary_tables`: a [s, d] table is nothing
    beside the activations) with the partner's sign already in the sine.
    Either pairing: channel j against j ^ 1 (`interleave`, the latent
    family) or against j +- d / 2 (halves). The partner is a lane roll
    either way and a select on one bit of the lane's index; no strided
    lane access, no permuted weight;
  - ONE cast to the compute dtype, on the way out.

Backward (`qk_prep_bwd`, the custom VJP's): the same pass transposed. The
rotation with the sine negated; the norm's from the input x, which it
re-reads (the product's output: no residual is added to what a layer's
checkpoint keeps); dx leaves in the COMPUTE dtype, as the operand of the
transposed products (which round a float32 cotangent to it anyway); the
scale's gradient leaves as float32 parts of 8 sublanes a grid step, summed
outside.

Grid (row, position block, head group), the head groups innermost so a
position block's tables are fetched once. A grid step holds `block_s`
positions of `PREP_LANES` lanes and works through them in slabs of whole
128-lane registers (two heads of 64 side by side) under a `fori_loop`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.pallas import tpu as pltpu

from . import pallas_call, pl
from ...parallel.spatial import current_mesh, shard_over_batch

F32 = jnp.float32
LANES = 128
SUBLANES = 8
#: Lanes of the product's output a grid step holds (float32: 2 MB at 512
#: positions), where the heads allow. Read on a v5e (PR 38, 20 runs chained
#: in one program, the block-diffusion cell's query [8192, 32 x 128], ms
#: forward / forward + backward at blocks of 512 positions): 128 lanes 0.43
#: / 0.86, 256: 0.35 / 0.67, 512: 0.32 / 0.60, 1024: 0.30 / 0.56 (201 +
#: 268 MB at the HBM's rate: 0.25 / 0.57); 2048 lanes of 256 positions 0.32
#: / 0.61; 1024 of 1024 does not fit the backward's VMEM.
PREP_LANES = 1024


def rotary_tables(positions, d: int, theta: float, interleave: bool,
                  lanes: int):
    """(cos, sin)[s, lanes] float32 for heads of d channels at
    `positions`[s], `lanes // d` heads side by side: channel j's angle is
    position * theta^(-2i/d) with i = j // 2 (`interleave`: pairs (2i,
    2i+1)) or j % (d/2) (halves: pairs (i, i + d/2)); the sine carries the
    rotation's sign (minus on a pair's first channel)."""
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = positions.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if interleave:
        cos = jnp.repeat(cos, 2, axis=1)
        sin = jnp.stack([-sin, sin], axis=-1).reshape(cos.shape)
    else:
        cos = jnp.concatenate([cos, cos], axis=1)
        sin = jnp.concatenate([-sin, sin], axis=1)
    return tuple(jnp.tile(t, (1, lanes // d)) for t in (cos, sin))


def _partner(x, off: int):
    """x[r, n] with each channel's rotation partner in its place: lane j
    takes lane j + off where bit `off` of j is clear, j - off where set."""
    n = x.shape[1]
    if 2 * off == n:
        return pltpu.roll(x, off, 1)
    lane = lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where((lane & off) == 0, pltpu.roll(x, n - off, 1),
                     pltpu.roll(x, off, 1))


def _slabs(width: int, d: int):
    """(lanes a slab, heads a slab, slabs) of a block `width` lanes wide."""
    unit = min(width, max(d, LANES))
    return unit, unit // d, width // unit


def _each_slab(n: int, unit: int, slab):
    """`slab(lanes, first_head_of)` for each of a block's n slabs: the body
    is traced ONCE whatever n is (a step's thirty calls are traced and
    lowered in every process that runs it, cached or not)."""
    if n == 1:
        return slab(slice(None), lambda per: 0)

    def body(p, _):
        slab(pl.ds(pl.multiple_of(p * unit, unit), unit), lambda per: p * per)

    lax.fori_loop(0, n, body, None)


def _fwd_kernel(*refs, d: int, off: int, eps):
    if eps is None:
        x_ref, cos_ref, sin_ref, o_ref = refs
    else:
        x_ref, cos_ref, sin_ref, g_ref, o_ref = refs
    unit, per, n = _slabs(x_ref.shape[1], d)

    def slab(lanes, first):
        x = x_ref[:, lanes]
        if eps is not None:
            x = x * lax.rsqrt(jnp.mean(x * x, axis=1, keepdims=True) + eps) \
                * g_ref[...]
        y = x * cos_ref[...] + _partner(x, off) * sin_ref[...]
        for j in range(per):
            o_ref[first(per) + j] = y[:, j * d:(j + 1) * d].astype(o_ref.dtype)

    _each_slab(n, unit, slab)


def _bwd_kernel(*refs, d: int, off: int, eps):
    if eps is None:
        dy_ref, cos_ref, sin_ref, dx_ref = refs
    else:
        x_ref, dy_ref, cos_ref, sin_ref, g_ref, dx_ref, dg_ref = refs
        dg_ref[...] = jnp.zeros_like(dg_ref)
    unit, per, n = _slabs(dx_ref.shape[1], d)

    def slab(lanes, first):
        heads = [dy_ref[first(per) + j].astype(F32) for j in range(per)]
        dy = heads[0] if per == 1 else jnp.concatenate(heads, axis=1)
        # the rotation transposed: the same pairs, the sine negated
        dn = dy * cos_ref[...] - _partner(dy, off) * sin_ref[...]
        if eps is not None:
            x = x_ref[:, lanes]
            r = lax.rsqrt(jnp.mean(x * x, axis=1, keepdims=True) + eps)
            xr = x * r
            dg_ref[...] += jnp.sum((dn * xr).reshape(-1, SUBLANES, unit),
                                   axis=0)
            dn = dn * g_ref[...]
            dn = r * (dn - xr * jnp.mean(dn * xr, axis=1, keepdims=True))
        dx_ref[:, lanes] = dn.astype(dx_ref.dtype)

    _each_slab(n, unit, slab)


def _group(heads: int, d: int) -> int:
    """Heads a grid step holds: `PREP_LANES` lanes' worth, fewer where
    that does not divide the heads."""
    hb = max(1, min(heads, PREP_LANES // d))
    while heads % hb:
        hb -= 1
    return hb


def _specs(heads: int, d: int, bs: int):
    """(heads a grid step, BlockSpecs of x[b, s, heads*d], y[b, heads, s,
    d], a table [s, unit] and the scale [1, d])."""
    hb = _group(heads, d)
    unit = _slabs(hb * d, d)[0]
    x = pl.BlockSpec((None, bs, hb * d), lambda b, i, j: (b, i, j))
    y = pl.BlockSpec((None, hb, bs, d), lambda b, i, j: (b, j, i, 0))
    table = pl.BlockSpec((bs, unit), lambda b, i, j: (i, 0))
    scale = pl.BlockSpec((1, d), lambda b, i, j: (0, 0))
    return hb, x, y, table, scale


_PARALLEL = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel"))


def _forward(x, cos, sin, scale, heads, off, eps, bs, dtype, interpret):
    b, s, w = x.shape
    d = w // heads
    hb, xs, ys, table, gs = _specs(heads, d, bs)
    norm = () if eps is None else (scale.reshape(1, d),)
    return pallas_call(
        functools.partial(_fwd_kernel, d=d, off=off, eps=eps),
        grid=(b, s // bs, heads // hb),
        in_specs=[xs, table, table] + [gs] * len(norm),
        out_specs=ys,
        out_shape=jax.ShapeDtypeStruct((b, heads, s, d), dtype),
        compiler_params=_PARALLEL, name="qk_prep_fwd", interpret=interpret,
    )(x, cos, sin, *norm)


def _backward(x, cos, sin, scale, dy, off, eps, bs, interpret):
    b, heads, s, d = dy.shape
    hb, xs, ys, table, gs = _specs(heads, d, bs)
    ns, nj = s // bs, heads // hb
    dx = jax.ShapeDtypeStruct((b, s, heads * d), dy.dtype)
    if eps is None:  # the rotation's transpose needs only the tables
        return pallas_call(
            functools.partial(_bwd_kernel, d=d, off=off, eps=None),
            grid=(b, ns, nj), in_specs=[ys, table, table],
            out_specs=xs, out_shape=dx, compiler_params=_PARALLEL,
            name="qk_prep_bwd", interpret=interpret,
        )(dy, cos, sin), None
    part = pl.BlockSpec((None, None, None, SUBLANES, d),
                        lambda b, i, j: (b, i, j, 0, 0))
    dx, dg = pallas_call(
        functools.partial(_bwd_kernel, d=d, off=off, eps=eps),
        grid=(b, ns, nj), in_specs=[xs, ys, table, table, gs],
        out_specs=[xs, part],
        out_shape=[dx, jax.ShapeDtypeStruct((b, ns, nj, SUBLANES, d), F32)],
        compiler_params=_PARALLEL, name="qk_prep_bwd", interpret=interpret,
    )(x, dy, cos, sin, scale.reshape(1, d))
    return dx, jnp.sum(dg, axis=(0, 1, 2, 3))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _prep(x, cos, sin, scale, heads, off, eps, bs, dtype, interpret):
    return _forward(x, cos, sin, scale, heads, off, eps, bs, dtype, interpret)


def _prep_fwd(x, cos, sin, scale, heads, off, eps, bs, dtype, interpret):
    y = _forward(x, cos, sin, scale, heads, off, eps, bs, dtype, interpret)
    # the norm's backward re-reads its input; the rotation's needs none
    return y, (None if eps is None else x, cos, sin, scale)


def _prep_bwd(heads, off, eps, bs, dtype, interpret, res, dy):
    x, cos, sin, scale = res
    dx, dg = _backward(x, cos, sin, scale, dy.astype(dtype), off, eps, bs,
                       interpret)
    return dx.astype(F32), jnp.zeros_like(cos), jnp.zeros_like(sin), dg


_prep.defvjp(_prep_fwd, _prep_bwd)


def qk_prep(x, positions, heads: int, theta: float, interleave: bool, dtype,
            block_s: int, scale=None, eps: float | None = None,
            interpret: bool = False):
    """x[b, s, heads * d] float32 (a projection's output) at rotary
    `positions`[s] -> [b, heads, s, d] in `dtype`: per-head RMS norm where
    `scale`[d] and `eps` are given, rotary positions, one cast, head-major
    (the module's docstring). d is 64 or a power of two of 128s; with the
    norm a multiple of 128; `block_s` divides s. Under a `mesh_context`
    the kernels run once per batch shard, tables and scale whole on each."""
    b, s, w = x.shape
    d = w // heads
    if d * heads != w or d & (d - 1) or d < LANES // 2 or s % block_s \
            or (scale is not None and d % LANES) \
            or (scale is None) != (eps is None):
        raise ValueError(f"qk_prep: no kernel for x{x.shape}, {heads} heads, "
                         f"blocks of {block_s} positions, norm {eps}")
    cos, sin = rotary_tables(positions, d, theta, interleave,
                             _slabs(_group(heads, d) * d, d)[0])
    off = 1 if interleave else d // 2

    def rows(x, cos, sin, scale):
        return _prep(x, cos, sin, scale, heads, off, eps, block_s,
                     jnp.dtype(dtype), interpret)

    return shard_over_batch(rows, current_mesh(), b, whole=3)(
        x, cos, sin, scale)
