"""The plain reference of one chip's share of Upstage's Solar Open 2 hybrid
decoder (upstage/Solar-Open2-250B, ``model_type`` ``solar_open2``): forward,
loss and gradients in straightforward float32 ``jax.numpy`` — no kernels, no
chunks, no inverse, no sort, no grouped product, no scan over stacks, matmuls
at "highest" precision (a TPU runs f32 matmuls in bf16 passes otherwise). The
family's modelling code could not be read here (there is no network); the
equations are those ISSUE 64 writes out from the catalog row's keys, from
Kimi Linear (arXiv:2510.26692: the recurrence and the decay WITHOUT a lower
bound), flash-linear-attention's KDA layer (the rank-128 pairs,
``allow_neg_eigval``) and the family's first model (the router), and the
configuration file lists every convention no key gives under ``assumed``.
With ``n(.)`` an RMSNorm of ``rms_norm_eps`` and a learned weight:

every layer: ``h = h + mixer(n_op(h))``, then ``h = h + ffn(n_ffn(h))``;
published layer ``i`` mixes with GQA where ``i`` is in ``gqa_layers`` and
with KDA otherwise, and EVERY layer ends in routed experts
(``first_k_dense_replace`` 0); the file's ``deployment.published_layers``
says which published layers the cut keeps; final RMSNorm; an untied head
over the vocabulary rows held here.

KDA (64 heads of 128, ``linear_attn_config``): ``q, k, v = silu(conv4(W_q
u)), silu(conv4(W_k u)), silu(conv4(W_v u))`` (depthwise, causal,
``short_conv_kernel_size`` taps, no bias); ``q``, ``k`` L2-normalised a
head, ``q`` times ``128^-0.5``; ``g_t = -exp(A_log_h) * softplus(W_fb (W_fa
u_t) + dt_bias)`` a channel, in (-inf, 0); ``beta_t = 2 sigmoid(W_beta
u_t)`` a head, in (0, 2); a head's state, TOKEN BY TOKEN in a ``lax.scan``:
``S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T``,
``o_t = S_t^T q_t``; the output an RMSNorm over each head's 128 values
times ``sigmoid(W_gb (W_ga u) + b_g)`` (one value a CHANNEL), then ``W_o``.

GQA (64 query heads over 8 key/value heads of 128): ``q, k, v = W_q u, W_k
u, W_v u`` with NO rotary turn and no norm; scores ``q . k / sqrt(128)``,
softmax in f32 over a causal mask, times ``v``; the result times
``sigmoid(W_g u)`` element by element (``use_gqa_gate``); ``W_o``. No cache.

Experts: ``s = sigmoid(z W_r)`` over all ``deployment.router_outputs``; the
top ``num_experts_per_tok`` of ``s + expert_bias`` (one group); the gates
``s`` (WITHOUT the bias) at the chosen over their sum + 1e-20
(``norm_topk_prob``) times ``routed_scaling_factor``; the output
``shared(z)`` plus the gated sum over the chosen experts THAT ARE HELD HERE
(``deployment.experts_held``: first and count). What the absent experts
would add is computed by nobody, here as in the program, and the partial sum
goes on to the next layer.

Departures, each without effect on the values: every held expert is computed
on ALL tokens and weighted by the token's gate for it (zero where it was not
chosen or is not held), in a Python loop over the held ones, rematerialised;
attention is taken one head and one block of queries at a time,
rematerialised; a KDA layer's heads go through their whole path 8 at a
time, rematerialised; the scan over positions is cut into blocks that are
rematerialised in the backward pass; the convolution is four shifted
products; and ``answers`` computes in BLOCKS as ``reference_ling.py``'s
does: a forward pass that keeps every half-layer's input, then half by half
backwards ``jax.vjp`` of that one half, the head in blocks of positions.

The parameter tree has the program's layout (``solar_init``) so that both
sides can be given the same seeded weights: ``embed`` [V,D], ``lm_head``
[D,V], ``final_norm`` [D], ``expert_bias`` [layers, 320], and under
``layers`` one stack a layer (``00_gqa_moe`` [1,...], ``01_kda_moe``
[1,...] ...). It shares no code with the program; it reads the
configuration file's keys.

Besides its answers it hands out its routing: per layer and token the
experts it chose, what each router was given (``router_in``), and ``p_kth``,
``p_next``: the k-th and (k+1)-th of ``s + expert_bias``.

As a script (a child of the ``bare_routed`` job, which may not touch JAX
while this holds the chip):

    python3 chipbench/reference_solar_open2.py <config.json> <sample.json> <out.npz>
"""

import json
import sys

import jax
import jax.numpy as jnp
import numpy as np

HEADS_AT_ONCE = 8  # heads of a mixer whose float32 path is held at once
SCAN_BLOCK = 256  # positions of the recurrence between two kept states
QUERY_BLOCK = 2048  # queries whose scores are held at once
HEAD_BLOCK = 8192  # positions whose logits are held at once
GATE_EPS = 1e-20
L2_EPS = 1e-6
BETA_MAX = 2.0  # kda_allow_neg_eigval


def kinds(cfg):
    """(mixer, feed-forward) of every kept layer."""
    first, last = cfg["deployment"]["published_layers"]
    return [("gqa" if i in cfg["gqa_layers"] else "kda", "moe") for i in range(first, last + 1)]


def where(cfg):
    """For every layer: (the name of its run's stack, its index in it): an
    expert layer is a stack of its own."""
    return [(f"{i:02d}_{kind[0]}_{kind[1]}", 0) for i, kind in enumerate(kinds(cfg))]

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


def _kda(u, w, cfg, dot):
    lin = cfg["linear_attn_config"]
    (B, T), dk = u.shape[:2], lin["head_dim"]  # d_k = d_v
    eps = cfg["rms_norm_eps"]
    unit = lambda m: m * jax.lax.rsqrt(  # noqa: E731
        jnp.sum(m * m, axis=-1, keepdims=True) + L2_EPS)

    @jax.checkpoint
    def some(u, wq, wk, wv, wfa, wfb, cq, ck, cv, dt_bias, a_log, wb, wga, wgb, bg):
        """Some heads' whole path, from their columns of the projections to
        their gated, normalised output [B,T,heads*dk]."""
        heads = lambda m: m.reshape(B, T, -1, dk)  # noqa: E731
        q, k, v = (heads(_conv_silu(dot(u, p), c)) for p, c in ((wq, cq), (wk, ck), (wv, cv)))
        g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(heads(dot(dot(u, wfa), wfb) + dt_bias))
        beta = BETA_MAX * jax.nn.sigmoid(dot(u, wb)) if cfg["kda_allow_neg_eigval"] \
            else jax.nn.sigmoid(dot(u, wb))
        o = delta_rule(unit(q) * dk ** -0.5, unit(k), v, g, beta)
        gate = jax.nn.sigmoid(dot(dot(u, wga), wgb) + bg)  # one value a channel
        return _rmsnorm(o, w["o_norm"], eps).reshape(B, T, -1) * gate

    outs, H = [], w["A_log"].shape[0]
    for lo in range(0, H, HEADS_AT_ONCE):
        hs, cs = slice(lo, lo + HEADS_AT_ONCE), slice(lo * dk, (lo + HEADS_AT_ONCE) * dk)
        outs.append(some(u, w["wq"][:, cs], w["wk"][:, cs], w["wv"][:, cs], w["w_fa"],
                         w["w_fb"][:, cs], w["conv_q"][:, cs], w["conv_k"][:, cs],
                         w["conv_v"][:, cs], w["dt_bias"][cs], w["A_log"][hs], w["w_beta"][:, hs],
                         w["w_ga"], w["w_gb"][:, cs], w["b_g"][cs]))
    return dot(jnp.concatenate(outs, axis=-1), w["wo"])


def _gqa(u, w, cfg, dot):
    H, KV, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    B, S = u.shape[:2]
    block = min(QUERY_BLOCK, S)
    pad = -S % block
    at = jnp.arange(S)

    @jax.checkpoint
    def block_of(q1, first, k1, v1):  # q1 [B,block,hd]; k1, v1 [B,S,hd]
        s = jnp.einsum("bqd,bkd->bqk", q1, k1) / np.sqrt(hd)
        seen = at[None, :] <= (first + jnp.arange(block))[:, None]
        return jnp.einsum("bqk,bkd->bqd",
                          jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1), v1)

    def head(qkv):  # one query head with its group's keys and values
        q1, k1, v1 = qkv
        blocks = jnp.moveaxis(
            jnp.pad(q1, ((0, 0), (0, pad), (0, 0))).reshape(B, -1, block, hd), 1, 0)
        firsts = jnp.arange(blocks.shape[0]) * block
        o = jax.lax.map(lambda x: block_of(x[0], x[1], k1, v1), (blocks, firsts))
        return jnp.moveaxis(o, 0, 1).reshape(B, -1, hd)[:, :S]

    @jax.checkpoint
    def some(u, wq, wk, wv, wg):
        """One key/value head's group of query heads, from their columns of
        the projections to their gated output [B,S,group*hd]; no positions."""
        q = dot(u, wq).reshape(B, S, -1, hd)
        k, v = dot(u, wk), dot(u, wv)  # [B,S,hd]: the group's one
        if cfg["use_rope"]:
            raise ValueError("use_rope: this reference turns nothing")
        group = q.shape[2]
        a = jax.lax.map(head, (jnp.moveaxis(q, 2, 0),
                               jnp.broadcast_to(k, (group,) + k.shape),
                               jnp.broadcast_to(v, (group,) + v.shape)))
        a = jnp.moveaxis(a, 0, 2).reshape(B, S, -1)
        return a * jax.nn.sigmoid(dot(u, wg)) if cfg["use_gqa_gate"] else a

    outs, group = [], H // KV
    for kv in range(KV):
        qs, ks = slice(kv * group * hd, (kv + 1) * group * hd), slice(kv * hd, (kv + 1) * hd)
        outs.append(some(u, w["wq"][:, qs], w["wk"][:, ks], w["wv"][:, ks], w["w_g"][:, qs]))
    return dot(jnp.concatenate(outs, axis=-1), w["wo"])


def _swiglu(x, wg, wu, wd, dot):
    return dot(jax.nn.silu(dot(x, wg)) * dot(x, wu), wd)


def _experts(x, weight_of, w, dot):
    """x [T,D]; weight_of [T,held]: a token's gate for each held expert,
    zero where it was not chosen -> sum over the held experts of gate *
    expert(x). Every held expert on every token, one after another,
    rematerialised."""
    @jax.checkpoint
    def one(x, wg, wu, wd, g):  # g [T]
        return g[:, None] * _swiglu(x, wg, wu, wd, dot)

    y = jnp.zeros_like(x)
    for e in range(w["w_gate"].shape[0]):
        y = y + one(x, w["w_gate"][e], w["w_up"][e], w["w_down"][e], weight_of[:, e])
    return y


def choose(scores, bias, cfg):
    """scores [T,E] (sigmoid, float32), bias [E] -> (the experts chosen
    [T,k], their gates [T,k], ``p_kth``, ``p_next`` [T]: the k-th and the
    (k+1)-th of ``scores + bias``)."""
    k = cfg["num_experts_per_tok"]
    top_p, top_i = jax.lax.top_k(scores + bias, k + 1)
    idx = top_i[:, :k]
    gates = jnp.take_along_axis(scores, idx, axis=-1)  # without the bias
    if cfg["norm_topk_prob"]:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + GATE_EPS)
    gates = gates * cfg["routed_scaling_factor"]
    return idx.astype(jnp.int32), gates, top_p[:, k - 1], top_p[:, k]

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
    return h + (_kda(u, w, cfg, dot) if kind[0] == "kda" else _gqa(u, w, cfg, dot))


def fed(kind, w, bias, h, cfg, dot=jnp.matmul, router_dot=jnp.matmul):
    """A layer's second half: ``h + ffn(n_ffn(h))`` -> (h, its routing)."""
    z = _rmsnorm(h, w["ffn_norm"], cfg["rms_norm_eps"])
    y, routing = _routed(z.reshape(-1, z.shape[-1]), w, bias, cfg, dot, router_dot)
    return h + y.reshape(h.shape), routing


def layer(kind, w, bias, h, cfg, **dots):
    """One layer, ``w`` its own weights (no leading axis), ``bias`` [E] its
    row of ``expert_bias`` -> (h, its routing)."""
    return fed(kind, w, bias, mixed(kind, w, h, cfg, **dots), cfg, **dots)


def _weights(params, cfg, i):
    """Layer ``i``'s weights in float32 and where they stand: (the name of
    its run's stack, its index in that stack, the weights, its bias row)."""
    name, at = where(cfg)[i]
    w = {k: v[at].astype(jnp.float32) for k, v in params["layers"][name].items()}
    return name, at, w, params["expert_bias"][i]


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

    # a layer is two programs forwards and two backwards, as
    # reference_ling.py's: the mixer's and the feed-forward's temporaries
    # need not fit the chip side by side
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
        sys.exit(f"chipbench/reference_solar_open2.py: no TPU ({jax.devices()[0].platform})")
    tokens, positions = check_sample(cfg, sample, cfg["recipe"]["seq_len"])
    # the program's own (bf16-rounded) trainable weights stay as they are, 2
    # bytes a parameter; ``answers`` upcasts one layer at a time
    params = jax.jit(lambda: init_(jax.random.PRNGKey(sample["seed"]), pc))()
    params["expert_bias"] = expert_bias(
        **cfg["recipe"]["expert_bias"], experts=cfg["deployment"]["router_outputs"],
        layers=cfg["num_hidden_layers"])
    np.savez(argv[2], platform=jax.devices()[0].platform,
             **answers(params, tokens, cfg, positions, sample))


if __name__ == "__main__":
    main(sys.argv[1:])
