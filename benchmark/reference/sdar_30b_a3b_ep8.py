"""Plain reference of `sdar_30b_a3b_ep8`: the decoder of
JetLM/SDAR-30B-A3B-Chat (`model_type: sdar_moe`) as its config.json and the
family's modeling code describe it, trained by diffusion over blocks
(arXiv:2503.09573), cut to ONE chip's share of an 8-chip expert-parallel
deployment: grouped-query attention (32 query heads over 4 key/value
heads of 128, an RMSNorm over each head's channels on q and on k, rotary
channel j against j + 64), every layer an expert layer with a softmax
router over all 128 experts (top-8, weights renormalised over the eight,
no bias buffer, no scaling, no shared expert), final RMSNorm, untied head.

The objective. A row `x0` of L ids, blocks of B = `block_length`
positions, b(p) = p // B; one `t_b ~ U(noise_t_lo, noise_t_hi)` a block,
`m_p ~ Bernoulli(t_b(p))`, `xt_p = mask_token_id if m_p else x0_p`. The
layers run over the 2L positions `[xt ; x0]`; the rotary position of an
index is its index inside its copy. Query (half, p) sees key (half', p')
iff: noised->noised b(p') = b(p); noised->clean b(p') < b(p); clean->clean
b(p') <= b(p); clean->noised never. The loss of the row is
`(1/L) sum_p m_p (1/t_b(p)) (-log softmax(z_p)[x0_p])` with z the logits
of the NOISED half, each position against its own id.

Straightforward `jax.numpy`, float32, matmul precision `highest`; no
kernel, no sorting, no grouped product, no compact list: the expert layer
is a dense loop over the experts held with a mask, the attention mask is
written out entry by entry from (half, block) of every position, a row is
taken whole. Nothing here imports the program and nothing takes a value
the program made, but one: the KEY each checked step's noise is drawn
from (`c["checked_noise_keys"]`, which the runner's tap reads off the
trainer's state before the step), from which `draw_noise` makes the
masks with the same two `jax.random` calls the objective states.

Departures from the published description, each also under `assumed` in
the configuration's file:
  - the share: only experts `first_expert .. + num_experts` of the 128 are
    held; a chosen expert that is absent adds nothing (its chip would),
    while the weights are still normalised over all eight chosen. Only
    `vocab_size` rows of embedding and head are held; ids, logits and loss
    are over them. `num_hidden_layers` is 5 of 48.
  - block length, noise schedule, the loss's weight and the mask id are
    not in config.json (the catalog's `not_given`): B 4, U(0.45, 0.95)
    with weight 1/t, no shift, the mask id the last row held.
  - attention crosses document boundaries inside a packed row (there are
    none); initialisation normal, sigma `init_std`, the embedding sigma
    `embed_std`: one base draw from the configuration's `weights.base_key`
    moved by the seed (`make_leaf`, the law of the other language-model
    reference, imported so that it is ONE law).
  - so that one row's backward fits beside 16 bytes a parameter, the
    training steps (`make_trainer`) keep only each layer's input, take the
    row's gradient one LAYER at a time (`jax.vjp` of `layer`, the
    cotangent handed down the stack; the five layers share one
    executable), and take the attention `HEAD_BLOCK` query heads at a
    time, each block's [8192, 8192] scores recomputed in its backward.
    Heads do not interact before the output product: the arithmetic and
    every value are the same. `row_loss` is the same chain written whole;
    a CPU test holds the two gradients equal.

Hooks, all `None`/off for the reference: `q` rounds the forward operands of
the products the program takes in bfloat16 (the lower-precision control).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from .kanana2_30b_a3b_ep8 import F32, HI, _q, make_leaf, mm, rmsnorm, swiglu

HEAD_BLOCK = 1


# --------------------------------------------------------------- parameters


def held_experts(c: dict) -> int:
    """`num_experts` as the family's config.json names it (the program's
    `lm` section holds the same count as `n_routed_experts`)."""
    return c["num_experts"] if "num_experts" in c else c["n_routed_experts"]


def router_width(c: dict) -> int:
    return c.get("n_routed_experts_published") or held_experts(c)


def is_expert_layer(c: dict, i: int) -> bool:
    return i not in c.get("mlp_only_layers", ()) and \
        (i + 1) % c.get("decoder_sparse_step", 1) == 0


def param_spec(c: dict) -> list[tuple[str, tuple, str]]:
    """[(path, shape, kind)]; kind: normal | embed | ones."""
    d, nh, g, hd = (c["hidden_size"], c["num_attention_heads"],
                    c["num_key_value_heads"], c["head_dim"])
    we, held = c["moe_intermediate_size"], held_experts(c)
    out = [("embedding", (c["vocab_size"], d), "embed")]
    for i in range(c["num_hidden_layers"]):
        L = f"layer_{i}"
        if not is_expert_layer(c, i):
            raise NotImplementedError("reference: every layer of this "
                                      "configuration is an expert layer")
        out += [(f"{L}/attn_norm/scale", (d,), "ones"),
                (f"{L}/gqa/wq", (d, nh * hd), "normal"),
                (f"{L}/gqa/wk", (d, g * hd), "normal"),
                (f"{L}/gqa/wv", (d, g * hd), "normal"),
                (f"{L}/gqa/q_norm/scale", (hd,), "ones"),
                (f"{L}/gqa/k_norm/scale", (hd,), "ones"),
                (f"{L}/gqa/wo", (nh * hd, d), "normal"),
                (f"{L}/ffn_norm/scale", (d,), "ones"),
                (f"{L}/moe/router", (d, router_width(c)), "normal"),
                (f"{L}/moe/experts_w_gate", (held, d, we), "normal"),
                (f"{L}/moe/experts_w_up", (held, d, we), "normal"),
                (f"{L}/moe/experts_w_down", (held, we, d), "normal")]
    out += [("final_norm/scale", (d,), "ones"),
            ("lm_head", (d, c["vocab_size"]), "normal")]
    return out


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


# ---------------------------------------------------------------- objective


def draw_noise(key, rows: int, c: dict, positions: int):
    """(m[rows, L] bool, t[rows, L]): one t a block of `block_length`
    positions, uniform on [noise_t_lo, noise_t_hi); m uniform < t. The
    whole batch at once, the block draw first: the order the objective
    states."""
    kt, km = jax.random.split(key)
    B = c["block_length"]
    t = jax.random.uniform(kt, (rows, -(-positions // B)), F32,
                           c["noise_t_lo"], c["noise_t_hi"])
    t = jnp.repeat(t, B, axis=1)[:, :positions]
    return jax.random.uniform(km, (rows, positions), F32) < t, t


def visible(L: int, B: int):
    """bool[2L, 2L], entry (query, key), written out from each position's
    (half, block): the four rules of the file's docstring."""
    clean = jnp.arange(2 * L) >= L
    blk = (jnp.arange(2 * L) % L) // B
    qc, kc = clean[:, None], clean[None, :]
    qb, kb = blk[:, None], blk[None, :]
    return ((~qc & ~kc & (kb == qb)) | (~qc & kc & (kb < qb))
            | (qc & kc & (kb <= qb)))


# ------------------------------------------------------------------- layers


def rope(x, theta: float, positions):
    """x[s, h, d] at `positions`[s]: channel j rotated against j + d/2 by
    position times theta^(-2j/d)."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = positions.astype(F32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def attend(qf, k, val, see, q=None):
    """qf[s, g, r, d] (r query heads to each of g key/value heads), k,
    val[s, g, d], see[s, s] -> [s, g, r, d]: softmax attention over the
    visible keys, all s x s scores of these heads."""
    scores = jnp.einsum("qgrd,kgd->grqk", _q(q, qf), _q(q, k), precision=HI) \
        / math.sqrt(qf.shape[-1])
    p = jax.nn.softmax(jnp.where(see, scores, -jnp.inf), axis=-1)
    return jnp.einsum("grqk,kgd->qgrd", _q(q, p), _q(q, val), precision=HI)


def gqa(v: dict, L: str, h, c: dict, see, positions, q=None, head_block=None):
    """h[s, d] -> [s, d]. `head_block`: query heads taken at a time, each
    block recomputed in its backward (memory only; None: all at once)."""
    s = h.shape[0]
    nh, g, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    eps, r = c["rms_norm_eps"], nh // g
    qq = rmsnorm(mm(h, v[f"{L}/gqa/wq"], q).reshape(s, nh, hd),
                 v[f"{L}/gqa/q_norm/scale"], eps)
    kk = rmsnorm(mm(h, v[f"{L}/gqa/wk"], q).reshape(s, g, hd),
                 v[f"{L}/gqa/k_norm/scale"], eps)
    qq, kk = rope(qq, c["rope_theta"], positions), rope(kk, c["rope_theta"], positions)
    val = mm(h, v[f"{L}/gqa/wv"], q).reshape(s, g, hd)
    # query head n reads key/value head n // r
    if head_block is None:
        o = attend(qq.reshape(s, g, r, hd), kk, val, see, q)
    else:
        # [blocks, s, 1, head_block, d] of queries beside each block's own
        # key/value head [blocks, s, 1, d], one block at a time
        hb = head_block
        group = jnp.arange(nh // hb) * hb // r
        qs = jnp.moveaxis(qq.reshape(s, nh // hb, 1, hb, hd), 1, 0)
        ks = jnp.moveaxis(kk, 1, 0)[group][:, :, None]
        vs = jnp.moveaxis(val, 1, 0)[group][:, :, None]
        o = jnp.moveaxis(lax.map(jax.checkpoint(
            lambda abc: attend(*abc, see, q)), (qs, ks, vs)), 0, 1)
    return mm(o.reshape(s, nh * hd), v[f"{L}/gqa/wo"], q)


def route(h, router, bias, c: dict):
    """(chosen[s, k], weights[s, k]): softmax over all the router's
    experts, the k largest (ties: the lower id), weighted by their
    probability over the sum of the k. `bias`: the family has no buffer
    (None), kept for the other reference's signature. Always float32."""
    p = jax.nn.softmax(jnp.matmul(h, router, precision=HI), axis=-1)
    chosen = jnp.argsort(-p, axis=-1, stable=True)[:, :c["num_experts_per_tok"]]
    w = jnp.take_along_axis(p, chosen, axis=-1)
    if c["norm_topk_prob"]:
        w = w / jnp.sum(w, -1, keepdims=True)
    return chosen, w


def moe(v: dict, L: str, h, c: dict, q=None, first=None, held=None,
        shared: bool = False, chosen_out: list | None = None, remat: bool = False):
    """The share's part of the expert layer: experts first..first+held (the
    configuration's own where not given). The family has no shared expert
    (`shared` is the other reference's argument and must stay false)."""
    assert not shared
    first = c.get("first_expert", 0) if first is None else first
    held = held_experts(c) if held is None else held
    chosen, w = route(h, v[f"{L}/moe/router"], None, c)
    if chosen_out is not None:
        chosen_out.append(chosen)

    def one_expert(y, ew):
        e, w_gate, w_up, w_down = ew
        # weight of expert first+e for each token: nought where not chosen
        we = jnp.sum(jnp.where(chosen == first + e, w, 0.0), axis=-1)
        return y + we[:, None] * swiglu(h, w_gate, w_up, w_down, q), None

    y, _ = lax.scan(jax.checkpoint(one_expert) if remat else one_expert,
                    jnp.zeros_like(h), (
        jnp.arange(held), v[f"{L}/moe/experts_w_gate"][:held],
        v[f"{L}/moe/experts_w_up"][:held], v[f"{L}/moe/experts_w_down"][:held]))
    return y


def layer(v: dict, i: int, x, c: dict, see, positions, q=None, chosen_out=None,
          head_block=None):
    L, eps = f"layer_{i}", c["rms_norm_eps"]
    x = x + gqa(v, L, rmsnorm(x, v[f"{L}/attn_norm/scale"], eps), c, see,
                positions, q, head_block)
    return x + moe(v, L, rmsnorm(x, v[f"{L}/ffn_norm/scale"], eps), c, q,
                   chosen_out=chosen_out, remat=head_block is not None)


def doubled(tokens, m, c: dict):
    """(ids[2L] = [xt ; x0], x0[L]) of a row of L + 1 ids (the last is
    unused) under the mask m[L]."""
    x0 = tokens[:m.shape[0]]
    return jnp.concatenate([jnp.where(m, c["mask_token_id"], x0), x0]), x0


def layout(L: int, c: dict):
    """(see[2L, 2L], rotary positions[2L]) of a doubled row."""
    return visible(L, c["block_length"]), jnp.arange(2 * L) % L


def head(v: dict, x, c: dict, q=None):
    return mm(rmsnorm(x, v["final_norm/scale"], c["rms_norm_eps"]),
              v["lm_head"], q)


def logits_row(v: dict, ids, c: dict, q=None, chosen_out=None, head_block=None):
    """ids[2L], the doubled row -> logits[L, vocab] of its noised half."""
    L = ids.shape[0] // 2
    see, positions = layout(L, c)
    x = v["embedding"][ids]
    for i in range(c["num_hidden_layers"]):
        x = layer(v, i, x, c, see, positions, q, chosen_out, head_block)
    return head(v, x[:L], c, q)


def masked_loss(logits, x0, m, t):
    """(1/L) sum_p m_p / t_p * -log softmax(logits_p)[x0_p]."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    hit = jnp.take_along_axis(logits, x0[:, None], axis=-1)[:, 0]
    return jnp.mean(m.astype(F32) / t * (lse - hit))


def row_loss(v: dict, tokens, c: dict, noise, q=None):
    """tokens[L + 1] under noise = (m[L], t[L])."""
    m, t = noise
    ids, x0 = doubled(tokens, m, c)
    return masked_loss(logits_row(v, ids, c, q), x0, m, t)


def chosen_experts(v: dict, tokens, c: dict, m=None) -> list:
    """The router's choices for one doubled row, [2L, k] an expert layer
    (m None: nothing masked). The heads in blocks, as the training steps
    take them: all 32 heads' [8192, 8192] scores at once are 8 GB."""
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
    layer at a time. Forward keeps each layer's input; the head gives the
    loss and the cotangent of its input (nought for the clean half, which
    bears no logits); each layer, last to first, is run again under
    `jax.vjp` and hands the cotangent down. Every layer's leaves are passed
    under layer 0's names, so one executable serves all. `acc`'s leaves
    are donated."""
    n = c["num_hidden_layers"]

    def as_first(tree: dict, i: int) -> dict:
        pre = f"layer_{i}/"
        return {f"layer_0/{k[len(pre):]}": x
                for k, x in tree.items() if k.startswith(pre)}

    def as_layer(tree: dict, i: int) -> dict:
        return {f"layer_{i}/{k[len('layer_0/'):]}": x for k, x in tree.items()}

    def run(p, x):
        return layer(p, 0, x, c, *layout(x.shape[0] // 2, c), q,
                     head_block=HEAD_BLOCK)

    forward = jax.jit(run)

    def backward(p, x, ct, acc, wgt):
        g, ct = jax.vjp(run, p, x)[1](ct)
        return {k: acc[k] + wgt * g[k] for k in acc}, ct

    backward = jax.jit(backward, donate_argnums=(3,))

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
        for i in range(n):
            xs.append(done(forward(as_first(values, i), xs[-1])))
        loss, g, ct = done(head_grad(
            {k: values[k] for k in HEAD_LEAVES}, xs.pop(), x0, m, t,
            {k: acc[k] for k in HEAD_LEAVES}, wgt))
        acc = {**acc, **g}
        for i in reversed(range(n)):
            g, ct = done(backward(as_first(values, i), xs.pop(), ct,
                                  as_first(acc, i), wgt))
            acc.update(as_layer(g, i))
        acc["embedding"] = embed_grad(ids, ct, acc["embedding"], wgt)
        return loss, acc

    return row_grad


def make_trainer(c: dict, hp: dict, q=None):
    """`steps(values, key, batches) -> readings`: len(batches) Adam steps
    in float32, one row at a time, the mean taken over the rows. `key` is
    the seed's key that `make_params` made the values from; step t's noise
    is drawn from `c["checked_noise_keys"][t - 1]`, the key the program's
    step drew its own from."""
    row_grad = make_row_grad(c, q)

    def adam(values, m, vv, g, t):
        b1, b2, eps, lr = hp["beta1"], hp["beta2"], hp["adam_eps"], hp["learning_rate"]
        out_p, out_m, out_v = {}, {}, {}
        for k in values:
            out_m[k] = b1 * m[k] + (1 - b1) * g[k]
            out_v[k] = b2 * vv[k] + (1 - b2) * jnp.square(g[k])
            mh = out_m[k] / (1 - b1 ** t)
            vh = out_v[k] / (1 - b2 ** t)
            out_p[k] = values[k] - lr * mh / (jnp.sqrt(vh) + eps)
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
