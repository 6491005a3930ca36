"""Plain reference of `kanana2_30b_a3b_ep8`: the decoder of
kakaocorp/kanana-2-30b-a3b-instruct-2601 (`model_type: deepseek_v3`) as its
config.json describes it, cut to ONE chip's share of an 8-chip
expert-parallel deployment: latent attention without query compression,
one dense SwiGLU layer, then expert layers with a sigmoid router over all
128 experts (top-6 of score + bias, weights renormalised over the six,
times 2.448), two shared experts, final RMSNorm, untied head, next-token
cross-entropy.

Straightforward `jax.numpy`, float32, matmul precision `highest`; no
kernel, no sorting, no blocking of attention: the expert layer is a dense
loop over the experts held with a mask, a row is taken whole. Nothing here
imports the program and nothing takes a value the program made.

Departures from the published description, each also under `assumed` in
the configuration's file:
  - the share: only experts `first_expert .. + n_routed_experts` of the 128
    are held; a chosen expert that is absent adds nothing (its chip would),
    while the weights are still normalised over all six chosen. Only
    `vocab_size` rows of embedding and head are held; ids, logits and loss
    are over them. `num_hidden_layers` is 5 of 48.
  - `e_score_correction_bias` is a fixed buffer (its update rule is not in
    the config); attention crosses document boundaries inside a packed row
    (there are none); initialisation normal, sigma `init_std`, the
    embedding sigma `embed_std`, the buffer sigma `bias_std`: one base
    draw from the configuration's `weights.base_key`, moved by the seed
    (`make_leaf`).
  - so that one row's backward fits beside 16 bytes a parameter, the
    training steps (`make_trainer`) keep only each layer's input and take
    the attention `HEAD_BLOCK` heads at a time, each block's scores
    recomputed in its backward ([32, 4096, 4096] float32 scores are 2.1 GB
    and a softmax's backward holds three of them). Heads do not interact
    before the output product: the arithmetic and every value are the same.
  - the training steps take the row's gradient one LAYER at a time
    (`jax.vjp` of `layer`, the cotangent handed down the stack), so that
    what is compiled is one layer of each kind and the head, not the whole
    row: the four expert layers share one executable, five small compiles
    (a minute on the chip) where the whole row was 0.34 GB of code, and
    the loaded executables' temporaries, which the chip's allocator keeps
    beside the 9.2 GB of arrays, are 3.5 GB in all at one head a block
    (6.1 GB at four; the sandbox's compile for a described v5e).
    Each call is waited for before the next is made, so no two calls'
    temporaries are asked for together.
    `row_loss` is the same chain written whole; a CPU test holds the two
    gradients equal.

Hooks, all `None`/off for the reference: `q` rounds the forward operands of
the products the program takes in bfloat16 (the lower-precision control).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
F32 = jnp.float32
HEAD_BLOCK = 1


# --------------------------------------------------------------- parameters


def is_expert_layer(c: dict, i: int) -> bool:
    return i >= c["first_k_dense_replace"] and i % c["moe_layer_freq"] == 0


def router_width(c: dict) -> int:
    return c.get("n_routed_experts_published") or c["n_routed_experts"]


def param_spec(c: dict) -> list[tuple[str, tuple, str]]:
    """[(path, shape, kind)]; kind: normal | embed | ones | bias."""
    d, nh = c["hidden_size"], c["num_attention_heads"]
    dn, dr, dv, r = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                     c["v_head_dim"], c["kv_lora_rank"])
    we, held = c["moe_intermediate_size"], c["n_routed_experts"]
    out = [("embedding", (c["vocab_size"], d), "embed")]
    for i in range(c["num_hidden_layers"]):
        L = f"layer_{i}"
        out += [(f"{L}/attn_norm/scale", (d,), "ones"),
                (f"{L}/mla/wq", (d, nh * (dn + dr)), "normal"),
                (f"{L}/mla/wkva", (d, r + dr), "normal"),
                (f"{L}/mla/kv_norm/scale", (r,), "ones"),
                (f"{L}/mla/wkvb", (r, nh * (dn + dv)), "normal"),
                (f"{L}/mla/wo", (nh * dv, d), "normal"),
                (f"{L}/ffn_norm/scale", (d,), "ones")]
        if is_expert_layer(c, i):
            ws = c["n_shared_experts"] * we
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


def make_leaf(c: dict, key, index: int, shape: tuple, kind: str):
    """One leaf from the seed's key and its place in `param_spec`: any leaf
    can be made again alone (the runner's tap and this file's `steps`
    measure a leaf's change against a fresh copy, not a kept one).

    Where the configuration has `weights` (`base_key`, `seed_jitter` j) a
    drawn leaf is std * (b + j*s) / sqrt(1 + j*j), b normal from the base
    key, which the file fixes, and s normal from the seed's: its law is
    N(0, std^2) as without, every seed moves every weight, and the routing,
    which sets the expert layers' work, is the base draw's on every seed.
    Without the entry the leaf is the seed's draw alone."""
    if kind == "ones":
        return jnp.ones(shape, F32)
    std = c[{"bias": "bias_std", "embed": "embed_std", "normal": "init_std"}[kind]]
    draw = jax.random.normal(jax.random.fold_in(key, index), shape, F32)
    w = c.get("weights")
    if w:
        j = float(w["seed_jitter"])
        base = jax.random.fold_in(jax.random.PRNGKey(int(w["base_key"])), index)
        draw = (jax.random.normal(base, shape, F32) + j * draw) / math.sqrt(1 + j * j)
    return std * draw


def make_params(c: dict, key) -> dict:
    return jax.jit(lambda k: {
        path: make_leaf(c, k, i, shape, kind)
        for i, (path, shape, kind) in enumerate(param_spec(c))})(key)


def change_norms(c: dict, values: dict, key) -> dict:
    """Per-leaf norm of `values` minus the initial leaf made again from the
    seed's key (and the configuration's base key), one jitted call; no
    second copy of the parameters is held."""
    return jax.jit(lambda v, k: {
        path: jnp.sqrt(jnp.sum(jnp.square(
            v[path].astype(F32) - make_leaf(c, k, i, shape, kind))))
        for i, (path, shape, kind) in enumerate(param_spec(c))})(values, key)


# ------------------------------------------------------------------- layers


def _q(q, x):
    return x if q is None else q(x)


def mm(x, w, q=None):
    return jnp.matmul(_q(q, x), _q(q, w), precision=HI)


def rmsnorm(x, scale, eps: float):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, theta: float):
    """x[s, h, d]: rotate the channel pairs (2i, 2i+1) (`rope_interleave`
    true) by position times theta^(-2i/d)."""
    s, d = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(s, dtype=F32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], -1).reshape(x.shape)


def attend(qf, k, val, q=None):
    """qf, k [s, h, dq], val [s, h, dv] -> [s, h, dv]: causal softmax
    attention over all s x s scores of these heads."""
    s = qf.shape[0]
    scores = jnp.einsum("qhd,khd->hqk", _q(q, qf), _q(q, k), precision=HI) \
        / math.sqrt(qf.shape[-1])
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", _q(q, p), _q(q, val), precision=HI)


def mla(v: dict, L: str, h, c: dict, q=None, head_block=None):
    """h[s, d] -> [s, d]. `head_block`: heads taken at a time, each
    block recomputed in its backward (memory only; None: all at once)."""
    s = h.shape[0]
    nh, dn, dr, dv, r = (c["num_attention_heads"], c["qk_nope_head_dim"],
                         c["qk_rope_head_dim"], c["v_head_dim"], c["kv_lora_rank"])
    qq = mm(h, v[f"{L}/mla/wq"], q).reshape(s, nh, dn + dr)
    q_nope = qq[..., :dn]
    q_rope = rope(qq[..., dn:], c["rope_theta"])
    ckv = mm(h, v[f"{L}/mla/wkva"], q)
    k_rope = rope(ckv[:, None, r:], c["rope_theta"])
    kv = mm(rmsnorm(ckv[:, :r], v[f"{L}/mla/kv_norm/scale"], c["rms_norm_eps"]),
            v[f"{L}/mla/wkvb"], q).reshape(s, nh, dn + dv)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_rope, (s, nh, dr))], -1)
    qf = jnp.concatenate([q_nope, q_rope], -1)
    val = kv[..., dn:]
    if head_block is None:
        o = attend(qf, k, val, q)
    else:
        # [s, h, d] -> [blocks, s, head_block, d], one block at a time
        split = lambda a: jnp.moveaxis(  # noqa: E731
            a.reshape(s, nh // head_block, head_block, a.shape[-1]), 1, 0)
        o = lax.map(jax.checkpoint(lambda abc: attend(*abc, q)),
                    (split(qf), split(k), split(val)))
        o = jnp.moveaxis(o, 0, 1).reshape(s, nh, dv)
    return mm(o.reshape(s, nh * dv), v[f"{L}/mla/wo"], q)


def swiglu(h, w_gate, w_up, w_down, q=None):
    return mm(jax.nn.silu(mm(h, w_gate, q)) * mm(h, w_up, q), w_down, q)


def route(h, router, bias, c: dict):
    """(chosen[s, k], weights[s, k]): the k largest of sigmoid score + bias
    (ties: the lower id), weighted by the score without the bias over the
    sum of the k, times the scaling factor. Always float32."""
    scores = jax.nn.sigmoid(jnp.matmul(h, router, precision=HI))
    k = c["num_experts_per_tok"]
    chosen = jnp.argsort(-(scores + lax.stop_gradient(bias)), axis=-1,
                         stable=True)[:, :k]
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    if c["norm_topk_prob"]:
        w = w / jnp.sum(w, -1, keepdims=True)
    return chosen, w * c["routed_scaling_factor"]


def moe(v: dict, L: str, h, c: dict, q=None, first=None, held=None,
        shared: bool = True, chosen_out: list | None = None, remat: bool = False):
    """The share's part of the expert layer: experts first..first+held (the
    configuration's own where not given), plus the shared expert."""
    first = c.get("first_expert", 0) if first is None else first
    held = c["n_routed_experts"] if held is None else held
    chosen, w = route(h, v[f"{L}/moe/router"], v[f"{L}/moe/bias"], c)
    if chosen_out is not None:
        chosen_out.append(chosen)
    def one_expert(y, ew):
        e, w_gate, w_up, w_down = ew
        # weight of expert first+e for each token: nought where not chosen
        we = jnp.sum(jnp.where(chosen == first + e, w, 0.0), axis=-1)
        return y + we[:, None] * swiglu(h, w_gate, w_up, w_down, q), None

    # every held expert on every token, one after the other (a loop the
    # compiler sees once: unrolled, 16 experts x 4 layers are a 1.3 GB program)
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
    x = x + mla(v, L, rmsnorm(x, v[f"{L}/attn_norm/scale"], eps), c, q, head_block)
    h = rmsnorm(x, v[f"{L}/ffn_norm/scale"], eps)
    if is_expert_layer(c, i):
        return x + moe(v, L, h, c, q, chosen_out=chosen_out,
                       remat=head_block is not None)
    return x + swiglu(h, v[f"{L}/ffn/w_gate"], v[f"{L}/ffn/w_up"],
                      v[f"{L}/ffn/w_down"], q)


def logits_row(v: dict, ids, c: dict, q=None, chosen_out=None):
    """ids[s] -> logits[s, vocab]."""
    x = v["embedding"][ids]
    for i in range(c["num_hidden_layers"]):
        x = layer(v, i, x, c, q, chosen_out)
    return head(v, x, c, q)


def head(v: dict, x, c: dict, q=None):
    return mm(rmsnorm(x, v["final_norm/scale"], c["rms_norm_eps"]),
              v["lm_head"], q)


def token_loss(logits, targets):
    """Mean over positions of -log softmax(logits_t)[target_t]."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    hit = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - hit)


def row_loss(v: dict, tokens, c: dict, q=None):
    """tokens[s + 1]: position t's logits against position t + 1's id."""
    return token_loss(logits_row(v, tokens[:-1], c, q), tokens[1:])


def chosen_experts(v: dict, tokens, c: dict) -> list:
    """The router's choices for one row, [s, k] an expert layer."""
    out: list = []
    logits_row(v, tokens[:-1], c, chosen_out=out)
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
    expert), so one executable serves every layer of a kind. `acc`'s
    leaves are donated."""
    n = c["num_hidden_layers"]
    kind = [next(j for j in range(n)
                 if is_expert_layer(c, j) == is_expert_layer(c, i))
            for i in range(n)]

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
    embed = jax.jit(lambda e, ids: e[ids])
    embed_grad = jax.jit(
        lambda ids, ct, acc, wgt: acc + wgt * jnp.zeros_like(acc).at[ids].add(ct),
        donate_argnums=(2,))

    def row_grad(values, tokens, acc, wgt):
        done = jax.block_until_ready
        ids = tokens[:-1]
        xs = [embed(values["embedding"], ids)]
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
