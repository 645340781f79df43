"""The plain reference of one chip's share of inclusionAI's Ling 3.0 hybrid
decoder (inclusionAI/Ling-3.0-flash, ``model_type`` ``bailing_hybrid``):
forward, loss and gradients in straightforward float32 ``jax.numpy`` — no
kernels, no chunks, no sort, no grouped product, no scan over stacks, matmuls
at "highest" precision (a TPU runs f32 matmuls in bf16 passes otherwise). The
family's modelling code is not public in a form that could be read here
(there is no network); the equations are those ISSUE 40 writes out from
Kimi Linear (arXiv:2510.26692: the recurrence), flash-linear-attention
(``safe_gate``: the decay with a lower bound) and DeepSeek-V3 (the latent
attention and the group-limited router), and the configuration file lists
every convention no key gives under ``assumed``. With ``n(.)`` an RMSNorm of
``rms_norm_eps`` and a learned weight:

every layer: ``h = h + mixer(n_op(h))``, then ``h = h + ffn(n_ffn(h))``;
published layer ``i`` mixes with MLA where ``(i + 1) % layer_group_size ==
0`` and with KDA otherwise, and has a dense SwiGLU where ``i <
first_k_dense_replace`` of the published model, routed experts otherwise
(the file's ``deployment.published_layers`` says which published layers the
cut keeps; the first kept one is the dense one); final RMSNorm; an untied
head over the vocabulary rows held here.

KDA (32 heads of 128): ``q, k, v = silu(conv4(W_q u)), silu(conv4(W_k u)),
silu(conv4(W_v u))`` (depthwise, causal, ``short_conv_kernel_size`` taps, no
bias); ``q``, ``k`` L2-normalised a head, ``q`` times ``128^-0.5``; ``g_t =
kda_lower_bound * sigmoid(exp(A_log_h) * (W_f u_t + dt_bias))`` a channel;
``beta_t = sigmoid(W_beta u_t)`` a head; a head's state, TOKEN BY TOKEN in a
``lax.scan``: ``S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t
k_t v_t^T``, ``o_t = S_t^T q_t``; the output an RMSNorm over each head's 128
values times ``sigmoid(W_g u)`` (one value a head), then ``W_o``.

MLA: ``q = W_q u`` a head 128 + 64; ``c, k_r = split(W_kva u)`` (512 + 64),
``c <- n(c)``; ``k_n, v = split(W_kvb c)`` a head 128 + 128; rotary on
``q``'s 64 and on ``k_r`` (shared by the heads), the stored values paired
(0, 1), (2, 3).. (``rope_interleave``); scores ``(q_n . k_n + q_r . k_r) /
sqrt(192)``, softmax in f32 over a causal mask, times ``v``; the same gate;
``W_o``. No cache.

Experts: ``s = sigmoid(z W_r)`` over all ``deployment.router_outputs``; the
decision on ``s + expert_bias``: each of ``n_group`` groups scored by the
sum of its best two, the best ``topk_group`` groups kept, the top
``num_experts_per_tok`` inside them; the gates ``s`` (WITHOUT the bias) at
the chosen over their sum (``norm_topk_prob``) times
``routed_scaling_factor``; the output ``shared(z)`` plus the gated sum over
the chosen experts THAT ARE HELD HERE (``deployment.experts_held``: first
and count). What the absent experts would add is computed by nobody, here as
in the program, and the partial sum goes on to the next layer.

Departures, each without effect on the values: every held expert is computed
on ALL tokens and weighted by the token's gate for it (zero where it was not
chosen or is not held), 2 experts at a time, rematerialised; attention is
taken one head and one block of queries at a time, rematerialised; a KDA
layer's heads go through their whole path 8 at a time, rematerialised; the scan
over positions is cut into blocks that are rematerialised in the backward
pass (the states of 32,768 positions are 69 GB); the convolution is four
shifted products; and ``answers`` computes in BLOCKS as
``reference_lfm2.py``'s does: a forward pass that keeps every layer's
input, then layer by layer backwards ``jax.vjp`` of that one layer, the
head in blocks of positions.

The parameter tree has the program's layout (``ling_init``) so that both
sides can be given the same seeded weights: ``embed`` [V,D], ``lm_head``
[D,V], ``final_norm`` [D], ``expert_bias`` [expert layers, 512], and under
``layers`` one stack for every run of like layers (``00_kda_dense`` [1,...],
``01_kda_moe`` [1,...] ...). It shares no code with the program; it reads the
configuration file's keys.

Besides its answers it hands out its routing: per expert layer and token
the experts it chose, what each router was given (``router_in``), and
``p_kth``, ``p_next``: the k-th and (k+1)-th of ``s + expert_bias`` inside
the kept groups, ``p_next`` raised to ``p_kth`` times the best dropped
group's score over the last kept one's where that is more: of the two
decisions a token's choice rests on, the groups' and the experts', the one
nearer a tie relative to its larger side (a token whose groups change
chooses other experts however clear its k-th was).

As a script (a child of the ``bare_routed`` job, which may not touch JAX
while this holds the chip):

    python3 chipbench/reference_ling.py <config.json> <sample.json> <out.npz>
"""

import json
import sys

import jax
import jax.numpy as jnp
import numpy as np

EXPERTS_AT_ONCE = 2  # [experts, T, D] float32 is 335 MB an expert at 32k
HEADS_AT_ONCE = 8  # heads of a mixer whose float32 path is held at once
SCAN_BLOCK = 256  # positions of the recurrence between two kept states
QUERY_BLOCK = 2048  # queries whose scores are held at once
HEAD_BLOCK = 8192  # positions whose logits are held at once
GATE_EPS = 1e-20
L2_EPS = 1e-6


def kinds(cfg):
    """(mixer, feed-forward) of every kept layer."""
    first, last = cfg["deployment"]["published_layers"]
    layers = range(first, last + 1)
    return [("mla" if (i + 1) % cfg["layer_group_size"] == 0 else "kda",
             "dense" if j < cfg["first_k_dense_replace"] else "moe")
            for j, i in enumerate(layers)]


def where(cfg):
    """For every layer: (the name of its run's stack, its index in it)."""
    out, run, ks = [], -1, kinds(cfg)
    for i, kind in enumerate(ks):
        if i and kind == ks[i - 1] and kind[1] == "dense":
            out.append((out[-1][0], out[-1][1] + 1))
        else:
            run += 1
            out.append((f"{run:02d}_{kind[0]}_{kind[1]}", 0))
    return out


def expert_bias(seed, scale, layers, experts):
    """The buffer both sides are given (``recipe.expert_bias`` of the
    configuration file): [expert layers, router outputs] float32."""
    return scale * jax.random.normal(
        jax.random.PRNGKey(seed), (layers, experts), jnp.float32)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _conv_silu(x, taps):
    k, T = taps.shape[0], x.shape[1]
    past = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return jax.nn.silu(sum(past[:, j:j + T] * taps[j] for j in range(k)))


def delta_rule(q, k, v, g, beta):
    """q, k, g [B,T,H,dk]; v [B,T,H,dv]; beta [B,T,H] -> o [B,T,H,dv]: the
    recurrence one position after another."""
    B, T, H, dk = q.shape

    def step(S, x):  # S [B,H,dk,dv]
        q_t, k_t, v_t, g_t, b_t = x
        S = jnp.exp(g_t)[..., None] * S
        seen = jnp.einsum("bhkv,bhk->bhv", S, k_t)
        S = S + (b_t[..., None] * k_t)[..., None] * (v_t - seen)[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t)

    @jax.checkpoint
    def block(S, xs):
        return jax.lax.scan(step, S, xs)

    pad = -T % SCAN_BLOCK  # positions of k = 0, g = 0, beta = 0 change nothing
    xs = [jnp.pad(m, ((0, 0), (0, pad)) + ((0, 0),) * (m.ndim - 2))
          for m in (q, k, v, g, beta)]
    xs = tuple(jnp.moveaxis(m, 1, 0).reshape((-1, SCAN_BLOCK) + m.shape[:1] + m.shape[2:])
               for m in xs)
    _, o = jax.lax.scan(block, jnp.zeros((B, H, dk, v.shape[-1]), jnp.float32), xs)
    return jnp.moveaxis(o.reshape((-1,) + o.shape[2:]), 0, 1)[:, :T]


def _head_gate(o, u, w_g, dot):
    return (o * jax.nn.sigmoid(dot(u, w_g))[..., None]).reshape(*o.shape[:2], -1)


def _kda(u, w, cfg, dot):
    (B, T), dk = u.shape[:2], cfg["head_dim"]  # d_k = d_v
    eps = cfg["rms_norm_eps"]
    unit = lambda m: m * jax.lax.rsqrt(  # noqa: E731
        jnp.sum(m * m, axis=-1, keepdims=True) + L2_EPS)

    @jax.checkpoint
    def some(u, wq, wk, wv, wf, cq, ck, cv, dt_bias, a_log, wb, wg):
        """Some heads' whole path, from their columns of the projections to
        their gated, normalised output [B,T,heads*dk]."""
        heads = lambda m: m.reshape(B, T, -1, dk)  # noqa: E731
        q, k, v = (heads(_conv_silu(dot(u, p), c)) for p, c in ((wq, cq), (wk, ck), (wv, cv)))
        g = cfg["kda_lower_bound"] * jax.nn.sigmoid(
            heads(dot(u, wf) + dt_bias) * jnp.exp(a_log)[:, None])
        o = delta_rule(unit(q) * dk ** -0.5, unit(k), v, g, jax.nn.sigmoid(dot(u, wb)))
        return _head_gate(_rmsnorm(o, w["o_norm"], eps), u, wg, dot)

    outs, H = [], w["A_log"].shape[0]
    for lo in range(0, H, HEADS_AT_ONCE):
        hs, cs = slice(lo, lo + HEADS_AT_ONCE), slice(lo * dk, (lo + HEADS_AT_ONCE) * dk)
        outs.append(some(u, w["wq"][:, cs], w["wk"][:, cs], w["wv"][:, cs], w["w_f"][:, cs],
                         w["conv_q"][:, cs], w["conv_k"][:, cs], w["conv_v"][:, cs],
                         w["dt_bias"][cs], w["A_log"][hs], w["w_beta"][:, hs], w["w_g"][:, hs]))
    return dot(jnp.concatenate(outs, axis=-1), w["wo"])


def _rotary(x, theta):
    """x [B,S,H,hd], its values paired (0, 1), (2, 3).. as stored
    (``rope_interleave``): each pair turned by its position's angle; the
    result lists the firsts of the pairs, then the seconds (both sides of a
    score are listed alike, so the order is without effect)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv  # [S,hd/2]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _mla(u, w, cfg, dot):
    H, dn, dr = cfg["num_attention_heads"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, r, eps = cfg["v_head_dim"], cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    B, S = u.shape[:2]
    ckr = dot(u, w["w_kva"])
    c = _rmsnorm(ckr[..., :r], w["kv_norm"], eps)
    k_r = _rotary(ckr[..., None, r:], cfg["rope_theta"])  # [B,S,1,dr]: all heads'
    block = min(QUERY_BLOCK, S)
    pad = -S % block
    at = jnp.arange(S)

    @jax.checkpoint
    def block_of(q1, first, k1, v1):  # q1 [B,block,d]; k1 [B,S,d]; v1 [B,S,dv]
        s = jnp.einsum("bqd,bkd->bqk", q1, k1) / np.sqrt(dn + dr)
        seen = at[None, :] <= (first + jnp.arange(block))[:, None]
        return jnp.einsum("bqk,bkd->bqd",
                          jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1), v1)

    def head(qkv):  # one head: [B,S,d], [B,S,d], [B,S,dv]
        q1, k1, v1 = qkv
        blocks = jnp.moveaxis(
            jnp.pad(q1, ((0, 0), (0, pad), (0, 0))).reshape(B, -1, block, dn + dr), 1, 0)
        firsts = jnp.arange(blocks.shape[0]) * block
        o = jax.lax.map(lambda x: block_of(x[0], x[1], k1, v1), (blocks, firsts))
        return jnp.moveaxis(o, 0, 1).reshape(B, -1, dv)[:, :S]

    @jax.checkpoint
    def some(u, c, k_r, wq, wkvb, wg):
        """Some heads' whole path, from their columns of the projections to
        their gated output [B,S,heads*dv]."""
        q = dot(u, wq).reshape(B, S, -1, dn + dr)
        kv = dot(c, wkvb).reshape(B, S, -1, dn + dv)
        q = jnp.concatenate([q[..., :dn], _rotary(q[..., dn:], cfg["rope_theta"])], axis=-1)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_r, kv.shape[:3] + (dr,))], axis=-1)
        a = jax.lax.map(head, tuple(jnp.moveaxis(m, 2, 0) for m in (q, k, kv[..., dn:])))
        return _head_gate(jnp.moveaxis(a, 0, 2), u, wg, dot)

    outs = []
    for lo in range(0, H, HEADS_AT_ONCE):
        hs = slice(lo, lo + HEADS_AT_ONCE)
        outs.append(some(u, c, k_r, w["wq"][:, lo * (dn + dr):(lo + HEADS_AT_ONCE) * (dn + dr)],
                         w["w_kvb"][:, lo * (dn + dv):(lo + HEADS_AT_ONCE) * (dn + dv)],
                         w["w_g"][:, hs]))
    return dot(jnp.concatenate(outs, axis=-1), w["wo"])


def _swiglu(x, wg, wu, wd, dot):
    return dot(jax.nn.silu(dot(x, wg)) * dot(x, wu), wd)


def _experts(x, weight_of, w, dot):
    """x [T,D]; weight_of [T,held]: a token's gate for each held expert,
    zero where it was not chosen -> sum over the held experts of gate *
    expert(x). Every held expert on every token, ``EXPERTS_AT_ONCE`` a time,
    rematerialised."""
    @jax.checkpoint
    def some(x, wg, wu, wd, g):  # wg, wu [e,D,H]; wd [e,H,D]; g [T,e]
        h = jax.nn.silu(dot(x, wg)) * dot(x, wu)  # [e,T,H]
        return jnp.sum(jnp.swapaxes(g, 0, 1)[..., None] * dot(h, wd), axis=0)

    y = jnp.zeros_like(x)
    for e in range(0, w["w_gate"].shape[0], EXPERTS_AT_ONCE):
        at = slice(e, e + EXPERTS_AT_ONCE)
        y = y + some(x, w["w_gate"][at], w["w_up"][at], w["w_down"][at], weight_of[:, at])
    return y


def choose(scores, bias, cfg):
    """scores [T,E] (sigmoid, float32), bias [E] -> (the experts chosen
    [T,k], their gates [T,k], ``p_kth``, ``p_next`` [T]: see the module's
    text)."""
    T, E = scores.shape
    k, groups, kept_n = cfg["num_experts_per_tok"], cfg["n_group"], cfg["topk_group"]
    decide = scores + bias if cfg["moe_router_enable_expert_bias"] else scores
    by_group = -jnp.sort(-decide.reshape(T, groups, E // groups), axis=-1)
    group_score = by_group[..., 0] + by_group[..., 1]  # the best two's sum
    order = jnp.argsort(-group_score, axis=-1)
    ranked = jnp.take_along_axis(group_score, order, axis=-1)
    kept = jnp.zeros((T, groups), bool).at[
        jnp.arange(T)[:, None], order[:, :kept_n]].set(True)
    inside = jnp.where(jnp.repeat(kept, E // groups, axis=1), decide, -jnp.inf)
    top_p, top_i = jax.lax.top_k(inside, k + 1)
    idx = top_i[:, :k]
    gates = jnp.take_along_axis(scores, idx, axis=-1)  # without the bias
    if cfg["norm_topk_prob"]:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + GATE_EPS)
    gates = gates * cfg["routed_scaling_factor"]
    p_k, p_n = top_p[:, k - 1], top_p[:, k]
    if kept_n < groups:  # the groups' tie where it is the nearer
        p_n = jnp.maximum(p_n, p_k * (ranked[:, kept_n] / ranked[:, kept_n - 1]))
    return idx.astype(jnp.int32), gates, p_k, p_n


def _routed(x, w, bias, cfg, dot, router_dot):
    """x [T,D] -> (the held experts' part of the layer's output plus the
    shared expert [T,D], its routing)."""
    first, held = cfg["deployment"]["experts_held"]
    scores = jax.nn.sigmoid(router_dot(x, w["router"]))  # [T, router outputs]
    idx, gates, p_k, p_n = choose(scores, bias, cfg)
    local = idx - first  # an absent expert's column is out of range: all zeros
    weight_of = jnp.sum(jax.nn.one_hot(local, held) * gates[..., None], axis=1)
    routing = {"routing": idx, "p_kth": p_k, "p_next": p_n, "router_in": x}
    y = _experts(x, weight_of, w, dot) + _swiglu(
        x, w["shared_gate"], w["shared_up"], w["shared_down"], dot)
    return y, jax.lax.stop_gradient(routing)


def mixed(kind, w, h, cfg, dot=jnp.matmul, **_):
    """A layer's first half: ``h + mixer(n_op(h))``."""
    u = _rmsnorm(h, w["norm"], cfg["rms_norm_eps"])
    return h + (_kda(u, w, cfg, dot) if kind[0] == "kda" else _mla(u, w, cfg, dot))


def fed(kind, w, bias, h, cfg, dot=jnp.matmul, router_dot=jnp.matmul):
    """A layer's second half: ``h + ffn(n_ffn(h))`` -> (h, its routing or
    None)."""
    z = _rmsnorm(h, w["ffn_norm"], cfg["rms_norm_eps"])
    if kind[1] == "dense":
        return h + _swiglu(z, w["w_gate"], w["w_up"], w["w_down"], dot), None
    y, routing = _routed(z.reshape(-1, z.shape[-1]), w, bias, cfg, dot, router_dot)
    return h + y.reshape(h.shape), routing


def layer(kind, w, bias, h, cfg, **dots):
    """One layer, ``w`` its own weights (no leading axis), ``bias`` [E] its
    row of ``expert_bias`` (None for a dense layer) -> (h, its routing or
    None)."""
    return fed(kind, w, bias, mixed(kind, w, h, cfg, **dots), cfg, **dots)


def _weights(params, cfg, i):
    """Layer ``i``'s weights in float32 and where they stand: (the name of
    its run's stack, its index in that stack, the weights, its bias row)."""
    name, at = where(cfg)[i]
    w = {k: v[at].astype(jnp.float32) for k, v in params["layers"][name].items()}
    moe = i - cfg["first_k_dense_replace"]
    bias = params["expert_bias"][moe] if moe >= 0 and "expert_bias" in params else None
    return name, at, w, bias


def _logits(lm_head, final_norm, h, cfg, dot):
    return dot(_rmsnorm(h, final_norm, cfg["rms_norm_eps"]), lm_head)


def forward(params, tokens, cfg, **dots):
    """tokens int [B,S] -> (logits f32 [B,S,V], the expert layers' routing,
    each stacked over them), all at once. ``dot`` multiplies activations by
    a weight matrix, ``router_dot`` by a router's; the tests pass ones of a
    lower precision to show that the check refuses them."""
    h, routed = params["embed"].astype(jnp.float32)[tokens], []
    for i, kind in enumerate(kinds(cfg)):
        _, _, w, bias = _weights(params, cfg, i)
        h, r = layer(kind, w, bias, h, cfg, **dots)
        if r is not None:
            routed.append(r)
    logits = _logits(params["lm_head"].astype(jnp.float32),
                     params["final_norm"].astype(jnp.float32), h, cfg,
                     dots.get("dot", jnp.matmul))
    return logits, {k: jnp.stack([r[k] for r in routed]) for k in routed[0]}


def loss(logits, targets):
    """Mean cross-entropy of logits[b, s] against targets[b, s], over the
    vocabulary rows held here."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))


def _sampled(flat, size, elements, offset=0):
    """Of a leaf of ``size`` elements, flattened, every k-th, k chosen so
    that at most ``elements`` leave the chip; ``flat`` holds the leaf's
    elements from ``offset`` on (one layer of a stacked leaf)."""
    every = -(-size // elements)
    return flat[-offset % every::every].astype(jnp.float32)


BY_EXPERT = "@expert_norms"
FFN_LEAVES = {"ffn_norm", "w_gate", "w_up", "w_down", "router", "shared_gate", "shared_up",
              "shared_down"}


def _expert_norms(g):
    """g [..., E, a, b], an expert leaf's gradient -> per layer the norm of
    each expert's matrix less the layer's mean over experts, flattened (a
    gate is a factor of its expert's whole gradient: gates from the wrong
    array or at the wrong scale move these norms, rounding leaves them)."""
    n = jnp.sqrt(jnp.sum(jnp.square(g.astype(jnp.float32)), axis=(-2, -1)))
    return (n - jnp.mean(n, axis=-1, keepdims=True)).reshape(-1)


def grad_answers(grads, sample):
    """Both sides' gradients as the check compares them: the global norm,
    and of each leaf named in ``sample["grad_leaves"]`` (a path in the
    parameter tree) every k-th element, or, with ``@expert_norms`` behind
    the path, :func:`_expert_norms` of it."""
    out = {"grad_norm": jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                                     for g in jax.tree_util.tree_leaves(grads)))}
    for path in sample["grad_leaves"]:
        g = grads
        for key in path.removesuffix(BY_EXPERT).split("."):
            g = g[key]
        out["grad." + path] = (
            _expert_norms(g) if path.endswith(BY_EXPERT)
            else _sampled(g.reshape(-1), g.size, sample["grad_elements"]))
    return out


def answers(params, tokens, cfg, positions, sample, **dots):
    """What the check compares: logits at ``positions`` of every sequence,
    the loss (targets = tokens, as the trainer feeds them), the global
    gradient norm of the trainable leaves, the sampled gradient leaves, and
    the routing: in blocks (see the module's text). ``params`` in any dtype,
    ``expert_bias`` among them; computed in f32."""
    ks = kinds(cfg)
    dot = dots.get("dot", jnp.matmul)
    wanted = {p: {} for p in sample["grad_leaves"]}  # path -> {layer: elements}

    def keep(path, leaf, g, at=0):
        if path in wanted:
            wanted[path][at] = _sampled(g.reshape(-1), leaf.size,
                                        sample["grad_elements"], at * g.size)
        if path + BY_EXPERT in wanted:
            wanted[path + BY_EXPERT][at] = _expert_norms(g)

    @jax.jit
    def head(lm_head, final_norm, h, targets):  # a block of positions: sums
        def f(lm_head, final_norm, h):
            logits = _logits(lm_head, final_norm, h, cfg, dot)
            logp = jax.nn.log_softmax(logits, axis=-1)
            return -jnp.sum(jnp.take_along_axis(logp, targets[..., None], axis=-1)), logits

        val, back, logits = jax.vjp(f, lm_head, final_norm, h, has_aux=True)
        return (val, logits) + back(jnp.ones((), jnp.float32) / tokens.size)

    # a layer is two programs forwards and two backwards: at 32k the mixer's
    # and the feed-forward's temporaries do not fit the chip side by side
    def back_of(f):
        def backwards(kind, w, bias, h, dh):
            _, back, _ = jax.vjp(lambda w, h: f(kind, w, bias, h), w, h, has_aux=True)
            dw, dh = back(dh)
            return dw, dh, sum(jnp.sum(jnp.square(g)) for g in jax.tree_util.tree_leaves(dw))

        return jax.jit(backwards, static_argnums=0)

    halves = [lambda kind, w, bias, h: (mixed(kind, w, h, cfg, **dots), None),
              lambda kind, w, bias, h: fed(kind, w, bias, h, cfg, **dots)]
    forwards = [jax.jit(f, static_argnums=0) for f in halves]
    backwards = [back_of(f) for f in halves]

    def leaves_of(w):  # each half's own: (the mixer's, the feed-forward's)
        return ({k: v for k, v in w.items() if k not in FFN_LEAVES},
                {k: v for k, v in w.items() if k in FFN_LEAVES})
    with jax.default_matmul_precision("highest"):
        embed = params["embed"].astype(jnp.float32)
        h, inputs, routed = embed[tokens], [], []
        for i, kind in enumerate(ks):  # the halves' inputs wait on the host
            _, _, w, bias = _weights(params, cfg, i)
            for half, own in zip(forwards, leaves_of(w)):
                inputs.append(np.asarray(h))
                h, r = half(kind, own, bias, h)
            if r is not None:
                routed.append({k: np.asarray(v) for k, v in r.items()})
        lm_head = params["lm_head"].astype(jnp.float32)
        final_norm = params["final_norm"].astype(jnp.float32)
        S = tokens.shape[1]
        val, d_head, d_norm, dhs, rows = 0.0, 0.0, 0.0, [], []
        for lo in range(0, S, HEAD_BLOCK):
            at = slice(lo, min(lo + HEAD_BLOCK, S))
            v, logits, dl, dn, dh = head(lm_head, final_norm, h[:, at], tokens[:, at])
            val, d_head, d_norm = val + v, d_head + dl, d_norm + dn
            dhs.append(dh)
            here = [p - lo for p in positions if at.start <= p < at.stop]
            rows.append(np.asarray(logits[:, np.asarray(here, int)]))
        del h, logits
        dh = jnp.concatenate(dhs, axis=1)
        squares = jnp.sum(jnp.square(d_norm)) + jnp.sum(jnp.square(d_head))
        keep("final_norm", d_norm, d_norm)
        keep("lm_head", d_head, d_head)
        del d_head
        for i in reversed(range(len(ks))):
            name, at, w, bias = _weights(params, cfg, i)
            for half, own in zip(reversed(backwards), reversed(leaves_of(w))):
                dw, dh, sq = half(ks[i], own, bias, jnp.asarray(inputs.pop()), dh)
                squares = squares + sq
                for key, g in dw.items():
                    keep(f"layers.{name}.{key}", params["layers"][name][key], g, at)
                del dw
        d_embed = jnp.zeros_like(embed).at[tokens].add(dh)
        squares = squares + jnp.sum(jnp.square(d_embed))
        keep("embed", embed, d_embed)
    missing = [p for p, got in wanted.items() if not got]
    if missing:
        raise KeyError(f"no gradient leaf {missing}")
    return {"logits": np.concatenate(rows, axis=1), "loss": float(val) / tokens.size,
            "grad_norm": np.asarray(jnp.sqrt(squares)),
            **{"grad." + p: np.concatenate([np.asarray(got[at]) for at in sorted(got)])
               for p, got in wanted.items()},
            **{k: np.stack([r[k] for r in routed]) for k in routed[0]}}


def check_sample(cfg, sample, seq):
    """The seeded sample both sides are run on (independent of --seed, so
    the reference's answers can be cached): tokens, drawn from the
    vocabulary rows held here, and sampled positions."""
    rng = np.random.RandomState(sample["seed"])
    tokens = rng.randint(0, cfg["vocab_size"], size=(sample["sequences"], seq))
    positions = np.unique(np.linspace(0, seq - 1, sample["positions"]).astype(int))
    return jnp.asarray(tokens, jnp.int32), positions


def main(argv):
    import os

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from chipbench import manifest

    with open(argv[0]) as f, open(argv[1]) as g:
        cfg, sample = json.load(f), json.load(g)
    # the program's init, for equal weights; a program that cannot express
    # the configuration ends here, before this process asks for the chip
    adapter = manifest.adapter_for(argv[0], cfg)
    init_, pc = adapter.program()[0], adapter.config(cfg)
    if jax.devices()[0].platform != "tpu":  # before any work: no CPU answers
        sys.exit(f"chipbench/reference_ling.py: no TPU ({jax.devices()[0].platform})")
    tokens, positions = check_sample(cfg, sample, cfg["recipe"]["seq_len"])
    # the program's own (bf16-rounded) trainable weights stay as they are, 2
    # bytes a parameter; ``answers`` upcasts one layer at a time
    params = jax.jit(lambda: init_(jax.random.PRNGKey(sample["seed"]), pc))()
    params["expert_bias"] = expert_bias(
        **cfg["recipe"]["expert_bias"], experts=cfg["deployment"]["router_outputs"],
        layers=cfg["num_hidden_layers"] - cfg["first_k_dense_replace"])
    np.savez(argv[2], platform=jax.devices()[0].platform,
             **answers(params, tokens, cfg, positions, sample))


if __name__ == "__main__":
    main(sys.argv[1:])
