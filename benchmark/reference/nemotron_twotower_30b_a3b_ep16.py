"""Plain reference of `nemotron_twotower_30b_a3b_ep16`: the layers of
nvidia/Nemotron-Labs-TwoTower-30B-A3B-Base-BF16 (`model_type: nemotron_h`)
as its config.json and the family's modeling code describe them, trained
by diffusion over blocks (arXiv:2503.09573), cut to ONE chip's share of a
16-chip expert-parallel deployment. Each layer is one mixer behind an
RMSNorm and a residual, picked by `hybrid_override_pattern`:

  - `M`, Mamba-2: `in_proj` -> z[H P], xBC[H P + 2 G N], dt[H]; xBC <-
    silu(causal depthwise conv(xBC) + bias), kernel `conv_kernel`; dt <-
    softplus(dt + dt_bias); A = -exp(A_log); per head n (group n // (H/G))
    the recurrence h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t, y_t = h_t
    C_t + D x_t; y <- RMSNorm over `n_groups` groups of y silu(z), a
    learned scale; `out_proj`;
  - `*`, attention: 32 query heads over 2 key/value heads of 128, q, k, v
    straight from their products (no rotary positions, no per-head norm),
    scale 1/sqrt(128);
  - `E`, experts: sigmoid scores over all 128 experts, the 6 largest of
    score + the fixed buffer, weights the scores renormalised over the six,
    times 2.5; each expert W_down relu(W_up h)^2, width 1856; a shared
    expert of the same form, width 3712, on every token.

Final RMSNorm, untied head.

The objective is `sdar_30b_a3b_ep8.py`'s (its noise draw, doubled row,
attention mask and loss are imported from there, so that they are ONE
statement): the layers run over the 2L positions `[xt ; x0]`. The
state-space layer's rule on that row: the clean half runs the recurrence
and the convolution over x0 from a zero state; a noised position p of
block b (first position bB) sees the path x0[0 .. bB-1] ++ xt[bB .. p]:
its convolution reads the clean copy before bB, its recurrence starts
from the CLEAN state at bB - 1 and runs over the block's noised positions.

Straightforward `jax.numpy`, float32, matmul precision `highest`; no
kernel, no chunking, no sorting: the recurrence is the per-position one
(`lax.scan` over positions), written without the program's chunked form;
the convolution gathers each tap's source row by its index on the path;
the expert layer is a dense loop over the experts held with a mask. The
clean state at each block's start is taken where the clean scan passes it
(one scan over blocks carries the clean state, and each block's noised
positions run from the carry as it enters the block), so no block start's
state is held beside another's; the scan runs in checkpointed stretches
of `STRETCH` blocks, so that its gradient keeps one state a stretch.
Nothing here imports the program and nothing takes a value the program
made but the noise key (`sdar_30b_a3b_ep8.py` says which).

Departures from the published description, each also under `assumed` in
the configuration's file: the share (experts 0..7 of 128 held, 1/8 of the
vocabulary, the first 9 of 52 layers); one tower, run over both copies of
the row, no adaLN; the initialisation (`make_leaf`). So that one row's
backward fits beside 16 bytes a parameter, `make_trainer` takes the row's
gradient one LAYER at a time as the other block-diffusion reference does,
one executable a kind of layer; `row_loss` is the same chain written
whole, and a CPU test holds the two gradients equal.

Hooks, all `None`/off for the reference: `q` rounds the forward operands of
the products the program takes in bfloat16, the scan's x, B and C among
them (the lower-precision control).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from . import kanana2_30b_a3b_ep8 as kanana
from . import sdar_30b_a3b_ep8 as sdar
from .kanana2_30b_a3b_ep8 import F32, HI, _q, mm, rmsnorm

draw_noise = sdar.draw_noise
doubled = sdar.doubled
masked_loss = sdar.masked_loss
visible = sdar.visible
attend = sdar.attend
HEAD_BLOCK = 1
#: blocks of the doubled row's scan taken between two kept states
STRETCH = 32


# --------------------------------------------------------------- parameters


def eps(c: dict) -> float:
    """`layer_norm_epsilon` as the family's config.json names it (the
    program's `lm` section holds it as `rms_norm_eps`)."""
    return c["layer_norm_epsilon"] if "layer_norm_epsilon" in c else c["rms_norm_eps"]


def router_width(c: dict) -> int:
    return c.get("n_routed_experts_published") or c["n_routed_experts"]


def kinds(c: dict) -> str:
    return c["hybrid_override_pattern"][:c["num_hidden_layers"]]


def shared_width(c: dict) -> int:
    return c.get("moe_shared_expert_intermediate_size") or \
        c["n_shared_experts"] * c["moe_intermediate_size"]


def param_spec(c: dict) -> list[tuple[str, tuple, str]]:
    """[(path, shape, kind)]; kind: normal | embed | ones | bias | conv |
    a_log | dt_bias."""
    d = c["hidden_size"]
    H, P, N, G, K = (c["mamba_num_heads"], c["mamba_head_dim"],
                     c["ssm_state_size"], c["n_groups"], c["conv_kernel"])
    nh, g, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    we, held, ws = c["moe_intermediate_size"], c["n_routed_experts"], shared_width(c)
    inner, width = H * P, H * P + 2 * G * N
    out = [("embedding", (c["vocab_size"], d), "embed")]
    for i, kind in enumerate(kinds(c)):
        L = f"layer_{i}"
        out.append((f"{L}/norm/scale", (d,), "ones"))
        if kind == "M":
            out += [(f"{L}/mamba/in_proj", (d, inner + width + H), "normal"),
                    (f"{L}/mamba/conv_w", (K, width), "conv"),
                    (f"{L}/mamba/conv_b", (width,), "conv"),
                    (f"{L}/mamba/dt_bias", (H,), "dt_bias"),
                    (f"{L}/mamba/A_log", (H,), "a_log"),
                    (f"{L}/mamba/D", (H,), "ones"),
                    (f"{L}/mamba/norm_scale", (inner,), "ones"),
                    (f"{L}/mamba/out_proj", (inner, d), "normal")]
        elif kind == "*":
            out += [(f"{L}/gqa/wq", (d, nh * hd), "normal"),
                    (f"{L}/gqa/wk", (d, g * hd), "normal"),
                    (f"{L}/gqa/wv", (d, g * hd), "normal"),
                    (f"{L}/gqa/wo", (nh * hd, d), "normal")]
        elif kind == "E":
            out += [(f"{L}/moe/router", (d, router_width(c)), "normal"),
                    (f"{L}/moe/bias", (router_width(c),), "bias"),
                    (f"{L}/moe/experts_w_up", (held, d, we), "normal"),
                    (f"{L}/moe/experts_w_down", (held, we, d), "normal"),
                    (f"{L}/moe/shared/w_up", (d, ws), "normal"),
                    (f"{L}/moe/shared/w_down", (ws, d), "normal")]
        else:
            raise NotImplementedError(f"reference: no layer kind {kind!r}")
    out += [("final_norm/scale", (d,), "ones"),
            ("lm_head", (d, c["vocab_size"]), "normal")]
    return out


def make_leaf(c: dict, key, index: int, shape: tuple, kind: str):
    """`kanana2_30b_a3b_ep8.make_leaf` for the normal, embedding, buffer
    and ones kinds; the state-space layer's own (the family's
    initialisation) from u uniform on [0, 1): with the configuration's
    `weights`, u = frac(b + j s), b from the base key and s from the seed's,
    which is again uniform: A_log = log(1 + 15 u) (A uniform on [1, 16]);
    dt_bias = dt + log(-expm1(-dt)), the inverse softplus of dt = max(
    exp(log tmin + u log(tmax / tmin)), floor); a convolution's weight and
    bias (2u - 1) / sqrt(conv_kernel)."""
    if kind in ("normal", "embed", "ones", "bias"):
        return kanana.make_leaf(c, key, index, shape, kind)
    u = jax.random.uniform(jax.random.fold_in(key, index), shape, F32)
    w = c.get("weights")
    if w:
        base = jax.random.fold_in(jax.random.PRNGKey(int(w["base_key"])), index)
        u = jnp.mod(jax.random.uniform(base, shape, F32)
                    + float(w["seed_jitter"]) * u, 1.0)
    if kind == "a_log":
        return jnp.log(1.0 + 15.0 * u)
    if kind == "dt_bias":
        lo, hi = math.log(c["time_step_min"]), math.log(c["time_step_max"])
        dt = jnp.maximum(jnp.exp(lo + u * (hi - lo)), c["time_step_floor"])
        return dt + jnp.log(-jnp.expm1(-dt))
    if kind == "conv":
        return (2.0 * u - 1.0) / math.sqrt(c["conv_kernel"])
    raise ValueError(kind)


def make_params(c: dict, key) -> dict:
    return jax.jit(lambda k: {
        path: make_leaf(c, k, i, shape, kind)
        for i, (path, shape, kind) in enumerate(param_spec(c))})(key)


def change_norms(c: dict, values: dict, key) -> dict:
    """Per-leaf norm of `values` minus the initial leaf made again from the
    seed's key (and the configuration's base key), one jitted call."""
    return jax.jit(lambda v, k: {
        path: jnp.sqrt(jnp.sum(jnp.square(
            v[path].astype(F32) - make_leaf(c, k, i, shape, kind))))
        for i, (path, shape, kind) in enumerate(param_spec(c))})(values, key)


# -------------------------------------------------------- state-space layer


def conv_sources(L: int, B: int, K: int):
    """(src[2L, K] int, valid[2L, K] bool): the row index of the doubled row
    that tap k (k positions back) of each position reads. A clean position
    p reads p - k of the clean copy; a noised one reads p - k of the noised
    copy from its block's first position on, of the clean copy before it;
    nothing before the row."""
    p = jnp.arange(2 * L)[:, None]
    k = jnp.arange(K)[None, :]
    clean, at = p >= L, p % L
    back = at - k
    mine = back >= at // B * B
    src = jnp.where(clean | ~mine, L + back, back)
    return jnp.clip(src, 0, 2 * L - 1), back >= 0


def conv(xbc, w, bias, L: int, c: dict):
    """xbc[2L, channels], w[K, channels] -> the depthwise convolution under
    the rule: y_p = bias + sum_k w[K-1-k] x[src(p, k)]."""
    K = w.shape[0]
    src, valid = conv_sources(L, c["block_length"], K)
    return bias + sum(jnp.where(valid[:, k, None], xbc[src[:, k]], 0.0)
                      * w[K - 1 - k] for k in range(K))


def recur(h, x, dt, b, cc, A):
    """Positions of one stretch from state h[H, P, N]: x[s, H, P], dt[s, H],
    b, cc[s, H, N] (each head's group already taken) -> (h after, y[s, H, P])."""
    def one(h, xs):
        xt, dtt, bt, ct = xs
        h = jnp.exp(dtt * A)[:, None, None] * h \
            + (dtt[:, None] * xt)[..., None] * bt[:, None, :]
        return h, jnp.einsum("hpn,hn->hp", h, ct, precision=HI)
    return lax.scan(one, h, (x, dt, b, cc))


def scan_doubled(x, dt, b, cc, A, L: int, B: int):
    """The recurrence on the doubled row: x[2L, H, P], dt[2L, H], b, cc
    [2L, H, N] -> y[2L, H, P]. One scan over the blocks carries the clean
    state; block j's clean positions advance it, and its noised positions
    run from the state as it enters the block."""
    if L % B:
        raise ValueError(f"reference: a copy of {L} in blocks of {B}")
    n = L // B
    blocks = lambda a: a.reshape(n, B, *a.shape[1:])  # noqa: E731
    noised = [blocks(a[:L]) for a in (x, dt, b, cc)]
    clean = [blocks(a[L:]) for a in (x, dt, b, cc)]

    def block(h, xs):
        nz, cl = xs[:4], xs[4:]
        _, yn = recur(h, *nz, A)
        h, yc = recur(h, *cl, A)
        return h, (yn, yc)

    stretch = STRETCH if n % STRETCH == 0 else 1
    group = lambda a: a.reshape(n // stretch, stretch, *a.shape[1:])  # noqa: E731

    @jax.checkpoint
    def part(h, xs):
        return lax.scan(block, h, xs)

    H, P, N = x.shape[1], x.shape[2], b.shape[-1]
    _, (yn, yc) = lax.scan(part, jnp.zeros((H, P, N), F32),
                           tuple(group(a) for a in (*noised, *clean)))
    return jnp.concatenate([yn.reshape(L, H, P), yc.reshape(L, H, P)])


def mamba(v: dict, L: str, h, c: dict, q=None):
    """h[2L, d], the doubled row -> [2L, d]. Between the two projections
    the layer is `n_groups` independent parts (a group's B and C, its
    heads' x, dt, z, convolution channels and norm), taken one at a time
    (`lax.map`), each recomputed in its backward: memory only."""
    s, d = h.shape
    H, P, N, G, K = (c["mamba_num_heads"], c["mamba_head_dim"],
                     c["ssm_state_size"], c["n_groups"], c["conv_kernel"])
    r, inner = H // G, H * P
    width = inner + 2 * G * N
    zxd = mm(h, v[f"{L}/mamba/in_proj"], q)
    # [channels] -> [G, channels a group], for x (and z), B and C alike
    split = lambda a, n: jnp.moveaxis(  # noqa: E731
        a.reshape(*a.shape[:-1], G, n), -2, 0)
    parts = lambda a: (split(a[..., :inner], r * P),  # noqa: E731
                       split(a[..., inner:inner + G * N], N),
                       split(a[..., inner + G * N:width], N))
    z = split(zxd[:, :inner], r * P)
    xbc = parts(zxd[:, inner:])
    dt = split(jax.nn.softplus(zxd[:, inner + width:] + v[f"{L}/mamba/dt_bias"]), r)
    w, bias = parts(v[f"{L}/mamba/conv_w"]), parts(v[f"{L}/mamba/conv_b"])
    A = -jnp.exp(v[f"{L}/mamba/A_log"]).reshape(G, r)
    skip = v[f"{L}/mamba/D"].reshape(G, r)
    scale = v[f"{L}/mamba/norm_scale"].reshape(G, r * P)

    def group(a):
        zg, xbcg, wg, biasg, dtg, Ag, Dg, scaleg = a
        x, b, cc = (jax.nn.silu(conv(u, wu, bu, s // 2, c))
                    for u, wu, bu in zip(xbcg, wg, biasg))
        x = x.reshape(s, r, P)
        heads = lambda u: jnp.broadcast_to(_q(q, u)[:, None], (s, r, N))  # noqa: E731
        y = scan_doubled(_q(q, x), dtg, heads(b), heads(cc), Ag, s // 2,
                         c["block_length"])
        y = (y + Dg[:, None] * x).reshape(s, r * P) * jax.nn.silu(zg)
        return rmsnorm(y, scaleg, eps(c))

    y = lax.map(jax.checkpoint(group), (z, xbc, w, bias, dt, A, skip, scale))
    return mm(jnp.moveaxis(y, 0, 1).reshape(s, inner),
              v[f"{L}/mamba/out_proj"], q)


# ------------------------------------------------------ attention, experts


def gqa(v: dict, L: str, h, c: dict, see, q=None, head_block=None):
    """h[2L, d] -> [2L, d] under `see`; query head n reads key/value head
    n // (heads / kv heads). `head_block`: query heads a block, each
    recomputed in its backward (memory only)."""
    s = h.shape[0]
    nh, g, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    r = nh // g
    qq = mm(h, v[f"{L}/gqa/wq"], q).reshape(s, nh, hd)
    kk = mm(h, v[f"{L}/gqa/wk"], q).reshape(s, g, hd)
    val = mm(h, v[f"{L}/gqa/wv"], q).reshape(s, g, hd)
    if head_block is None:
        o = attend(qq.reshape(s, g, r, hd), kk, val, see, q)
    else:
        hb = head_block
        grp = jnp.arange(nh // hb) * hb // r
        qs = jnp.moveaxis(qq.reshape(s, nh // hb, 1, hb, hd), 1, 0)
        ks = jnp.moveaxis(kk, 1, 0)[grp][:, :, None]
        vs = jnp.moveaxis(val, 1, 0)[grp][:, :, None]
        o = jnp.moveaxis(lax.map(jax.checkpoint(
            lambda abc: attend(*abc, see, q)), (qs, ks, vs)), 0, 1)
    return mm(o.reshape(s, nh * hd), v[f"{L}/gqa/wo"], q)


def relu2(h, w_up, w_down, q=None):
    return mm(jnp.square(jnp.maximum(mm(h, w_up, q), 0.0)), w_down, q)


def moe(v: dict, L: str, h, c: dict, q=None, first=None, held=None,
        shared: bool = True, chosen_out: list | None = None, remat: bool = False):
    """The share's part of the expert layer: experts first..first+held (the
    configuration's own where not given), plus the shared expert."""
    first = c.get("first_expert", 0) if first is None else first
    held = c["n_routed_experts"] if held is None else held
    chosen, w = kanana.route(h, v[f"{L}/moe/router"], v[f"{L}/moe/bias"], c)
    if chosen_out is not None:
        chosen_out.append(chosen)

    def one_expert(y, ew):
        e, w_up, w_down = ew
        we = jnp.sum(jnp.where(chosen == first + e, w, 0.0), axis=-1)
        return y + we[:, None] * relu2(h, w_up, w_down, q), None

    y = jnp.zeros_like(h)
    if held:
        y, _ = lax.scan(jax.checkpoint(one_expert) if remat else one_expert, y, (
            jnp.arange(held), v[f"{L}/moe/experts_w_up"][:held],
            v[f"{L}/moe/experts_w_down"][:held]))
    if shared:
        y = y + relu2(h, v[f"{L}/moe/shared/w_up"], v[f"{L}/moe/shared/w_down"], q)
    return y


# ------------------------------------------------------------------- layers


def layer(v: dict, i: int, kind: str, x, c: dict, see, q=None, chosen_out=None,
          head_block=None):
    L = f"layer_{i}"
    h = rmsnorm(x, v[f"{L}/norm/scale"], eps(c))
    if kind == "M":
        return x + mamba(v, L, h, c, q)
    if kind == "*":
        return x + gqa(v, L, h, c, see, q, head_block)
    return x + moe(v, L, h, c, q, chosen_out=chosen_out,
                   remat=head_block is not None)


def head(v: dict, x, c: dict, q=None):
    return mm(rmsnorm(x, v["final_norm/scale"], eps(c)), v["lm_head"], q)


def logits_row(v: dict, ids, c: dict, q=None, chosen_out=None, head_block=None):
    """ids[2L], the doubled row -> logits[L, vocab] of its noised half."""
    L = ids.shape[0] // 2
    see = visible(L, c["block_length"])
    x = v["embedding"][ids]
    for i, kind in enumerate(kinds(c)):
        x = layer(v, i, kind, x, c, see, q, chosen_out, head_block)
    return head(v, x[:L], c, q)


def row_loss(v: dict, tokens, c: dict, noise, q=None):
    """tokens[L + 1] under noise = (m[L], t[L])."""
    m, t = noise
    ids, x0 = doubled(tokens, m, c)
    return masked_loss(logits_row(v, ids, c, q), x0, m, t)


def chosen_experts(v: dict, tokens, c: dict, m=None) -> list:
    """The router's choices for one doubled row, [2L, k] an expert layer
    (m None: nothing masked); attention a head at a time."""
    out: list = []
    m = jnp.zeros((tokens.shape[0] - 1,), bool) if m is None else m
    logits_row(v, doubled(tokens, m, c)[0], c, chosen_out=out,
               head_block=HEAD_BLOCK)
    return out


# ----------------------------------------------------------------- training


HEAD_LEAVES = ("final_norm/scale", "lm_head")


def make_row_grad(c: dict, q=None):
    """`row_grad(values, tokens, noise, acc, wgt) -> (loss of the row, acc
    + wgt * its gradient)`: `row_loss`'s gradient by the chain rule, a
    layer at a time, as `sdar_30b_a3b_ep8.make_row_grad`: each layer's
    leaves are passed under the names of the first layer of its kind, so
    one executable serves every layer of a kind. `acc`'s leaves are
    donated."""
    pattern = kinds(c)
    first = {k: pattern.index(k) for k in set(pattern)}

    def as_first(tree: dict, i: int) -> dict:
        pre, to = f"layer_{i}/", f"layer_{first[pattern[i]]}/"
        return {to + k[len(pre):]: x for k, x in tree.items() if k.startswith(pre)}

    def as_layer(tree: dict, i: int) -> dict:
        pre = f"layer_{first[pattern[i]]}/"
        return {f"layer_{i}/{k[len(pre):]}": x for k, x in tree.items()}

    def run_of(kind):
        def run(p, x):
            L = x.shape[0] // 2
            return layer(p, first[kind], kind, x, c,
                         visible(L, c["block_length"]), q,
                         head_block=HEAD_BLOCK)
        return run

    forward = {k: jax.jit(run_of(k)) for k in first}

    def backward_of(kind):
        run = run_of(kind)

        def backward(p, x, ct, acc, wgt):
            g, ct = jax.vjp(run, p, x)[1](ct)
            return {k: acc[k] + wgt * g[k] for k in acc}, ct
        return jax.jit(backward, donate_argnums=(3,))

    backward = {k: backward_of(k) for k in first}

    def head_grad(p, x, x0, m, t, acc, wgt):
        L = x0.shape[0]
        loss, (g, ct) = jax.value_and_grad(
            lambda pp, xx: masked_loss(head(pp, xx[:L], c, q), x0, m, t),
            argnums=(0, 1))(p, x)
        return loss, {k: acc[k] + wgt * g[k] for k in acc}, ct

    head_grad = jax.jit(head_grad, donate_argnums=(5,))
    embed = jax.jit(lambda e, ids: e[ids])
    embed_grad = jax.jit(
        lambda ids, ct, acc, wgt: acc + wgt * jnp.zeros_like(acc).at[ids].add(ct),
        donate_argnums=(2,))

    def row_grad(values, tokens, noise, acc, wgt):
        done = jax.block_until_ready
        m, t = noise
        ids, x0 = doubled(tokens, m, c)
        xs = [embed(values["embedding"], ids)]
        for i, kind in enumerate(pattern):
            xs.append(done(forward[kind](as_first(values, i), xs[-1])))
        loss, g, ct = done(head_grad(
            {k: values[k] for k in HEAD_LEAVES}, xs.pop(), x0, m, t,
            {k: acc[k] for k in HEAD_LEAVES}, wgt))
        acc = {**acc, **g}
        for i in reversed(range(len(pattern))):
            g, ct = done(backward[pattern[i]](as_first(values, i), xs.pop(), ct,
                                              as_first(acc, i), wgt))
            acc.update(as_layer(g, i))
        acc["embedding"] = embed_grad(ids, ct, acc["embedding"], wgt)
        return loss, acc

    return row_grad


def make_trainer(c: dict, hp: dict, q=None):
    """`steps(values, key, batches) -> readings`: len(batches) Adam steps
    in float32, one row at a time, the mean taken over the rows, as
    `sdar_30b_a3b_ep8.make_trainer` takes them: step t's noise is drawn
    from `c["checked_noise_keys"][t - 1]`, the key the program's step drew
    its own from."""
    row_grad = make_row_grad(c, q)

    def adam(values, m, vv, g, t):
        b1, b2, ep, lr = hp["beta1"], hp["beta2"], hp["adam_eps"], hp["learning_rate"]
        out_p, out_m, out_v = {}, {}, {}
        for k in values:
            out_m[k] = b1 * m[k] + (1 - b1) * g[k]
            out_v[k] = b2 * vv[k] + (1 - b2) * jnp.square(g[k])
            mh = out_m[k] / (1 - b1 ** t)
            vh = out_v[k] / (1 - b2 ** t)
            out_p[k] = values[k] - lr * mh / (jnp.sqrt(vh) + ep)
        return out_p, out_m, out_v

    adam = jax.jit(adam, donate_argnums=(0, 1, 2))
    norms = jax.jit(lambda t: {k: jnp.sqrt(jnp.sum(jnp.square(x)))
                               for k, x in t.items()})

    def steps(values: dict, key, batches: list) -> dict:
        noise_keys = c["checked_noise_keys"]
        if len(noise_keys) < len(batches):
            raise ValueError("reference: a noise key for every checked step "
                             "is needed (`checked_noise_keys`)")
        m = {k: jnp.zeros_like(x) for k, x in values.items()}
        vv = {k: jnp.zeros_like(x) for k, x in values.items()}
        losses, row_losses, grad_norms, masked = [], [], None, []
        for t, tokens in enumerate(batches, start=1):
            n = tokens.shape[0]
            noise = draw_noise(jnp.asarray(noise_keys[t - 1], jnp.uint32), n, c,
                               tokens.shape[1] - 1)
            masked.append(float(jnp.mean(noise[0].astype(F32))))
            g = {k: jnp.zeros_like(x) for k, x in values.items()}
            per_row = []
            for i in range(n):
                lb, g = row_grad(values, jnp.asarray(tokens[i]),
                                 (noise[0][i], noise[1][i]), g, 1.0 / n)
                per_row.append(float(lb))
            losses.append(sum(per_row) / n)
            row_losses.append(per_row)
            if t == 1:
                grad_norms = {k: float(x) for k, x in norms(g).items()}
            values, m, vv = adam(values, m, vv, g, float(t))
            del g  # before the next step's zeros: a fifth copy does not fit
        dparam = {k: float(x) for k, x in change_norms(c, values, key).items()}
        return {"losses": losses, "row_losses": row_losses,
                "grad_norms": grad_norms, "dparam_norms": dparam,
                "masked_share": masked}

    return steps
