"""Plain reference of `trinity_mini_ep16`: the decoder of arcee-ai/Trinity-Mini
(`model_type: afmoe`) as its config.json and the family's modeling code
describe it, cut to ONE chip's share of a 16-chip expert-parallel
deployment, trained by next-token cross-entropy.

A layer, by its published index p (`published_layers`), with a the
normed stream:
  - `a = RMSNorm_in(x)`; `q = RMSNorm_h(a Wq)`, `k = RMSNorm_h(a Wk)` (one
    learned scale each over a head's 128 channels), `v = a Wv`;
  - on a window layer (`layer_types[p]` "sliding_attention") rotary
    positions on q and k, channel j against j + 64, theta `rope_theta`;
    a full layer ("full_attention") has no positional encoding;
  - `o = softmax(q . k / sqrt(128) over the visible keys) v`, query head n
    reading key/value head n // 8: key k is visible to query q iff k <= q,
    and on a window layer also q - k < `sliding_window`;
  - `o <- o * sigmoid(a Wg)`, then `x <- x + RMSNorm_post_attn(o Wo)`;
  - `x <- x + RMSNorm_post_mlp(F(RMSNorm_pre_mlp(x)))`, F a SwiGLU of
    `intermediate_size` on the first `num_dense_layers` published layers
    and after them the expert layer: sigmoid scores over all 128
    experts, the 8 largest of score + bias chosen, their scores over
    their sum (`route_norm`) times `route_scale`, each chosen expert a
    SwiGLU of `moe_intermediate_size`, plus `num_shared_experts` shared
    SwiGLU of that width on every token.
The embedding is multiplied by sqrt(hidden_size) (`mup_enabled`); final
RMSNorm; untied head.

Straightforward `jax.numpy`, float32, matmul precision `highest`; no
kernel, no sorting, no grouped product: the expert layer is a dense loop
over the experts held with a mask; the attention is taken a block of
`QUERY_BLOCK` queries at a time against the keys at or before its last
query (on a window layer from its first query's earliest visible key on),
the mask of each block written out entry by entry from the statement
above. Nothing here imports the program and nothing takes a value the
program made.

Departures from the published description, each also under `assumed` in
the configuration's file:
  - the share: only experts `first_expert .. + num_experts` of the 128 are
    held; a chosen expert that is absent adds nothing (its chip would),
    while the weights are still normalised over all eight chosen. Only
    `vocab_size` rows of embedding and head are held; ids, logits and loss
    are over them. Five layers: published layers 0 and 4..7.
  - the router's bias is a fixed buffer (its update rule, and the balance
    coefficient `load_balance_coeff`, are read by nothing); no logit
    scaling; attention crosses document boundaries inside a packed row;
    initialisation normal, sigma `init_std`, the embedding sigma
    `embed_std`, the buffer sigma `bias_std`: one base draw from
    `weights.base_key` moved by the seed (`make_leaf`, the other
    language-model references' law, imported so that it is ONE law).
  - so that one row's backward fits beside 16 bytes a parameter, the
    training steps keep only each layer's input, take the row's gradient
    one LAYER at a time (`jax.vjp` of `layer`, the cotangent handed down;
    layers of one kind share one executable), and take the attention
    `HEAD_BLOCK` query heads and `QUERY_BLOCK` queries at a time, each
    recomputed in its backward. Heads and query blocks do not interact
    before the output product: the arithmetic and every value are the
    same. `row_loss` is the same chain written whole; a CPU test holds the
    two gradients equal.

Hooks, all `None`/off for the reference: `q` rounds the forward operands of
the products the program takes in bfloat16 (the lower-precision control).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from .kanana2_30b_a3b_ep8 import F32, HI, _q, make_leaf, mm, rmsnorm, swiglu
from .sdar_30b_a3b_ep8 import rope

HEAD_BLOCK = 1
QUERY_BLOCK = 2048


# --------------------------------------------------------------- parameters


def published(c: dict, i: int) -> int:
    kept = c.get("published_layers")
    return kept[i] if kept else i


def is_expert_layer(c: dict, i: int) -> bool:
    return published(c, i) >= c["num_dense_layers"]


def is_window_layer(c: dict, i: int) -> bool:
    kind = c["layer_types"][published(c, i)]
    assert kind in ("sliding_attention", "full_attention"), kind
    return kind == "sliding_attention"


def attention_name(c: dict, i: int) -> str:
    return "swa" if is_window_layer(c, i) else "gqa"


def held_experts(c: dict) -> int:
    return c["num_experts"] if "num_experts" in c else c["n_routed_experts"]


def router_width(c: dict) -> int:
    return c.get("n_routed_experts_published") or held_experts(c)


def param_spec(c: dict) -> list[tuple[str, tuple, str]]:
    """[(path, shape, kind)]; kind: normal | embed | ones | bias."""
    d, nh, g, hd = (c["hidden_size"], c["num_attention_heads"],
                    c["num_key_value_heads"], c["head_dim"])
    we, held = c["moe_intermediate_size"], held_experts(c)
    out = [("embedding", (c["vocab_size"], d), "embed")]
    for i in range(c["num_hidden_layers"]):
        L, A = f"layer_{i}", f"layer_{i}/{attention_name(c, i)}"
        out += [(f"{L}/attn_norm/scale", (d,), "ones"),
                (f"{A}/wq", (d, nh * hd), "normal"),
                (f"{A}/wk", (d, g * hd), "normal"),
                (f"{A}/wv", (d, g * hd), "normal"),
                (f"{A}/q_norm/scale", (hd,), "ones"),
                (f"{A}/k_norm/scale", (hd,), "ones"),
                (f"{A}/wg", (d, nh * hd), "normal"),
                (f"{A}/wo", (nh * hd, d), "normal"),
                (f"{L}/attn_post_norm/scale", (d,), "ones"),
                (f"{L}/ffn_norm/scale", (d,), "ones"),
                (f"{L}/ffn_post_norm/scale", (d,), "ones")]
        if is_expert_layer(c, i):
            ws = c["num_shared_experts"] * we
            out += [(f"{L}/moe/router", (d, router_width(c)), "normal"),
                    (f"{L}/moe/bias", (router_width(c),), "bias"),
                    (f"{L}/moe/experts_w_gate", (held, d, we), "normal"),
                    (f"{L}/moe/experts_w_up", (held, d, we), "normal"),
                    (f"{L}/moe/experts_w_down", (held, we, d), "normal"),
                    (f"{L}/moe/shared/w_gate", (d, ws), "normal"),
                    (f"{L}/moe/shared/w_up", (d, ws), "normal"),
                    (f"{L}/moe/shared/w_down", (ws, d), "normal")]
        else:
            w = c["intermediate_size"]
            out += [(f"{L}/ffn/w_gate", (d, w), "normal"),
                    (f"{L}/ffn/w_up", (d, w), "normal"),
                    (f"{L}/ffn/w_down", (w, d), "normal")]
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


# ------------------------------------------------------------------- layers


def attend(qf, k, val, window: int | None, q=None):
    """qf[s, g, r, d] (r query heads to each of g key/value heads), k,
    val[s, g, d] -> [s, g, r, d]: softmax attention, a block of
    QUERY_BLOCK queries at a time (each recomputed in its backward)
    against the keys at or before its last query; `window` W: only keys
    of the last W positions up to the query's own."""
    s, d = qf.shape[0], qf.shape[-1]

    def block(q0: int, k0: int, qb, kb, vb):
        qpos = q0 + jnp.arange(qb.shape[0])[:, None]
        kpos = k0 + jnp.arange(kb.shape[0])[None, :]
        see = kpos <= qpos
        if window is not None:
            see = see & (qpos - kpos < window)
        scores = jnp.einsum("qgrd,kgd->grqk", _q(q, qb), _q(q, kb),
                            precision=HI) / math.sqrt(d)
        p = jax.nn.softmax(jnp.where(see, scores, -jnp.inf), axis=-1)
        return jnp.einsum("grqk,kgd->qgrd", _q(q, p), _q(q, vb), precision=HI)

    outs = []
    for q0 in range(0, s, QUERY_BLOCK):
        q1 = min(s, q0 + QUERY_BLOCK)
        k0 = 0 if window is None else max(0, q0 - window + 1)
        outs.append(jax.checkpoint(block, static_argnums=(0, 1))(
            q0, k0, qf[q0:q1], k[k0:q1], val[k0:q1]))
    return jnp.concatenate(outs, axis=0)


def attention(v: dict, i: int, a, c: dict, q=None, head_block=None):
    """a[s, d], the normed stream -> o Wo [s, d] (before its post-norm).
    `head_block`: query heads taken at a time, each block recomputed in
    its backward (memory only; None: all at once)."""
    s = a.shape[0]
    A = f"layer_{i}/{attention_name(c, i)}"
    nh, g, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    eps, r = c["rms_norm_eps"], nh // g
    qq = rmsnorm(mm(a, v[f"{A}/wq"], q).reshape(s, nh, hd),
                 v[f"{A}/q_norm/scale"], eps)
    kk = rmsnorm(mm(a, v[f"{A}/wk"], q).reshape(s, g, hd),
                 v[f"{A}/k_norm/scale"], eps)
    window = c["sliding_window"] if is_window_layer(c, i) else None
    if window is not None:
        positions = jnp.arange(s)
        qq, kk = rope(qq, c["rope_theta"], positions), rope(kk, c["rope_theta"], positions)
    val = mm(a, v[f"{A}/wv"], q).reshape(s, g, hd)
    # query head n reads key/value head n // r
    if head_block is None:
        o = attend(qq.reshape(s, g, r, hd), kk, val, window, q)
    else:
        # [blocks, s, 1, head_block, d] of queries beside each block's own
        # key/value head [blocks, s, 1, d], one block at a time
        hb = head_block
        group = jnp.arange(nh // hb) * hb // r
        qs = jnp.moveaxis(qq.reshape(s, nh // hb, 1, hb, hd), 1, 0)
        ks = jnp.moveaxis(kk, 1, 0)[group][:, :, None]
        vs = jnp.moveaxis(val, 1, 0)[group][:, :, None]
        o = jnp.moveaxis(lax.map(jax.checkpoint(
            lambda abc: attend(*abc, window, q)), (qs, ks, vs)), 0, 1)
    o = o.reshape(s, nh * hd) * jax.nn.sigmoid(mm(a, v[f"{A}/wg"], q))
    return mm(o, v[f"{A}/wo"], q)


def route(h, router, bias, c: dict):
    """(chosen[s, k], weights[s, k]): the k largest of sigmoid score + bias
    (ties: the lower id), weighted by the score without the bias over the
    sum of the k (`route_norm`), times `route_scale`. Always float32."""
    scores = jax.nn.sigmoid(jnp.matmul(h, router, precision=HI))
    k = c["num_experts_per_tok"]
    chosen = jnp.argsort(-(scores + lax.stop_gradient(bias)), axis=-1,
                         stable=True)[:, :k]
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    if c["route_norm"]:
        w = w / jnp.sum(w, -1, keepdims=True)
    return chosen, w * c["route_scale"]


def moe(v: dict, L: str, h, c: dict, q=None, first=None, held=None,
        shared: bool = True, chosen_out: list | None = None, remat: bool = False):
    """The share's part of the expert layer: experts first..first+held (the
    configuration's own where not given), plus the shared expert."""
    first = c.get("first_expert", 0) if first is None else first
    held = held_experts(c) if held is None else held
    chosen, w = route(h, v[f"{L}/moe/router"], v[f"{L}/moe/bias"], c)
    if chosen_out is not None:
        chosen_out.append(chosen)

    def one_expert(y, ew):
        e, w_gate, w_up, w_down = ew
        # weight of expert first+e for each token: nought where not chosen
        we = jnp.sum(jnp.where(chosen == first + e, w, 0.0), axis=-1)
        return y + we[:, None] * swiglu(h, w_gate, w_up, w_down, q), None

    y = jnp.zeros_like(h)
    if held:
        y, _ = lax.scan(jax.checkpoint(one_expert) if remat else one_expert, y, (
            jnp.arange(held), v[f"{L}/moe/experts_w_gate"][:held],
            v[f"{L}/moe/experts_w_up"][:held], v[f"{L}/moe/experts_w_down"][:held]))
    if shared:
        y = y + swiglu(h, v[f"{L}/moe/shared/w_gate"], v[f"{L}/moe/shared/w_up"],
                       v[f"{L}/moe/shared/w_down"], q)
    return y


def layer(v: dict, i: int, x, c: dict, q=None, chosen_out=None, head_block=None):
    L, eps = f"layer_{i}", c["rms_norm_eps"]
    a = rmsnorm(x, v[f"{L}/attn_norm/scale"], eps)
    x = x + rmsnorm(attention(v, i, a, c, q, head_block),
                    v[f"{L}/attn_post_norm/scale"], eps)
    h = rmsnorm(x, v[f"{L}/ffn_norm/scale"], eps)
    if is_expert_layer(c, i):
        y = moe(v, L, h, c, q, chosen_out=chosen_out, remat=head_block is not None)
    else:
        y = swiglu(h, v[f"{L}/ffn/w_gate"], v[f"{L}/ffn/w_up"],
                   v[f"{L}/ffn/w_down"], q)
    return x + rmsnorm(y, v[f"{L}/ffn_post_norm/scale"], eps)


def embed(emb, ids, c: dict):
    x = emb[ids]
    return x * math.sqrt(c["hidden_size"]) if c.get("mup_enabled") else x


def head(v: dict, x, c: dict, q=None):
    return mm(rmsnorm(x, v["final_norm/scale"], c["rms_norm_eps"]),
              v["lm_head"], q)


def logits_row(v: dict, ids, c: dict, q=None, chosen_out=None, head_block=None):
    """ids[s] -> logits[s, vocab]."""
    x = embed(v["embedding"], ids, c)
    for i in range(c["num_hidden_layers"]):
        x = layer(v, i, x, c, q, chosen_out, head_block)
    return head(v, x, c, q)


def token_loss(logits, targets):
    """Mean over positions of -log softmax(logits_t)[target_t]."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    hit = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - hit)


def row_loss(v: dict, tokens, c: dict, q=None):
    """tokens[s + 1]: position t's logits against position t + 1's id."""
    return token_loss(logits_row(v, tokens[:-1], c, q), tokens[1:])


def chosen_experts(v: dict, tokens, c: dict) -> list:
    """The router's choices for one row, [s, k] an expert layer (the heads
    in blocks, as the training steps take them)."""
    out: list = []
    logits_row(v, tokens[:-1], c, chosen_out=out, head_block=HEAD_BLOCK)
    return out


# ----------------------------------------------------------------- training


HEAD_LEAVES = ("final_norm/scale", "lm_head")


def make_row_grad(c: dict, q=None):
    """`row_grad(values, tokens, acc, wgt) -> (loss of the row, acc + wgt
    * its gradient)`: `row_loss`'s gradient by the chain rule, a layer at
    a time. Forward keeps each layer's input; the head gives the loss and
    the cotangent of its input; each layer, last to first, is run again
    under `jax.vjp` and hands the cotangent down. Layer i's leaves are
    passed under the name of the FIRST layer of its kind (dense or
    expert, window or full), so one executable serves every layer of a
    kind. `acc`'s leaves are donated."""
    n = c["num_hidden_layers"]
    sort = [(is_expert_layer(c, i), is_window_layer(c, i)) for i in range(n)]
    kind = [sort.index(sort[i]) for i in range(n)]

    def as_kind(tree: dict, i: int) -> dict:
        pre = f"layer_{i}/"
        return {f"layer_{kind[i]}/{k[len(pre):]}": x
                for k, x in tree.items() if k.startswith(pre)}

    def as_layer(tree: dict, i: int) -> dict:
        pre = f"layer_{kind[i]}/"
        return {f"layer_{i}/{k[len(pre):]}": x for k, x in tree.items()}

    def run(j):
        return lambda p, x: layer(p, j, x, c, q, head_block=HEAD_BLOCK)

    forward = jax.jit(lambda j, p, x: run(j)(p, x), static_argnums=0)

    def backward(j, p, x, ct, acc, wgt):
        g, ct = jax.vjp(run(j), p, x)[1](ct)
        return {k: acc[k] + wgt * g[k] for k in acc}, ct

    backward = jax.jit(backward, static_argnums=0, donate_argnums=(4,))

    def head_grad(p, x, tokens, acc, wgt):
        loss, (g, ct) = jax.value_and_grad(
            lambda pp, xx: token_loss(head(pp, xx, c, q), tokens[1:]),
            argnums=(0, 1))(p, x)
        return loss, {k: acc[k] + wgt * g[k] for k in acc}, ct

    head_grad = jax.jit(head_grad, donate_argnums=(3,))
    embed_fwd = jax.jit(lambda e, ids: embed(e, ids, c))
    embed_grad = jax.jit(lambda ids, ct, acc, wgt: acc + wgt * jax.vjp(
        lambda e: embed(e, ids, c), acc)[1](ct)[0], donate_argnums=(2,))

    def row_grad(values, tokens, acc, wgt):
        done = jax.block_until_ready
        ids = tokens[:-1]
        xs = [embed_fwd(values["embedding"], ids)]
        for i in range(n):
            xs.append(done(forward(kind[i], as_kind(values, i), xs[-1])))
        loss, g, ct = done(head_grad(
            {k: values[k] for k in HEAD_LEAVES}, xs.pop(), tokens,
            {k: acc[k] for k in HEAD_LEAVES}, wgt))
        acc = {**acc, **g}
        for i in reversed(range(n)):
            g, ct = done(backward(kind[i], as_kind(values, i), xs.pop(), ct,
                                  as_kind(acc, i), wgt))
            acc.update(as_layer(g, i))
        acc["embedding"] = embed_grad(ids, ct, acc["embedding"], wgt)
        return loss, acc

    return row_grad


def make_trainer(c: dict, hp: dict, q=None):
    """`steps(values, key, batches) -> readings`: len(batches) Adam steps
    in float32, one row at a time, the mean taken over the rows. `key` is
    the seed's key that `make_params` made the values from."""
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
        m = {k: jnp.zeros_like(x) for k, x in values.items()}
        vv = {k: jnp.zeros_like(x) for k, x in values.items()}
        losses, row_losses, grad_norms = [], [], None
        for t, tokens in enumerate(batches, start=1):
            n = tokens.shape[0]
            g = {k: jnp.zeros_like(x) for k, x in values.items()}
            per_row = []
            for i in range(n):
                lb, g = row_grad(values, jnp.asarray(tokens[i]), g, 1.0 / n)
                per_row.append(float(lb))
            losses.append(sum(per_row) / n)
            row_losses.append(per_row)
            if t == 1:
                grad_norms = {k: float(x) for k, x in norms(g).items()}
            values, m, vv = adam(values, m, vv, g, float(t))
            del g  # before the next step's zeros: a fifth copy does not fit
        dparam = {k: float(x) for k, x in change_norms(c, values, key).items()}
        return {"losses": losses, "row_losses": row_losses,
                "grad_norms": grad_norms, "dparam_norms": dparam}

    return steps
