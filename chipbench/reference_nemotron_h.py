"""The plain reference of one chip's share of NVIDIA's Nemotron-H hybrid
decoder (nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16, ``model_type``
``nemotron_h``): forward, loss and gradients in straightforward float32
``jax.numpy`` — no kernels, no chunks, no sort, no grouped product, no scan
over stacks, matmuls at "highest" precision (a TPU runs f32 matmuls in bf16
passes otherwise). The family's modelling code could not be read here
(there is no network); the equations are those ISSUE 52 writes out from the
catalog row's keys and from what is remembered of transformers'
``modeling_nemotron_h.py`` and of Mamba-2 (arXiv:2405.21060), and the
configuration file lists every convention no key gives under ``assumed``.
With ``n(.)`` an RMSNorm of ``layer_norm_epsilon`` and a learned weight:

the stack: ``h = embed[tokens]``; for each character of
``hybrid_override_pattern``: ``h = h + branch(n(h))``, ONE branch a layer;
final RMSNorm; an untied head over the vocabulary rows held here.

``M`` (Mamba-2; ``H = mamba_num_heads`` heads of ``P = mamba_head_dim``,
``d_inner = H P``, ``G = n_groups``, ``N = ssm_state_size``): ``z, xBC, dt =
split(u W_in)`` (``d_inner``, ``d_inner + 2 G N``, ``H``); ``xBC =
silu(conv(xBC) + b)`` (depthwise, causal, ``conv_kernel`` taps); ``x, B, C =
split(xBC)``; ``dt = softplus(dt + dt_bias)``; a head's state ``[P, N]``,
POSITION BY POSITION in a ``lax.scan``: ``S_t = exp(dt_t a) S_{t-1} + dt_t
x_t (x) B_t[group]``, ``y_t = S_t C_t[group] + D x_t`` with ``a =
-exp(A_log)`` a head; then ``(y silu(z))`` normalised over each of the ``G``
groups of ``d_inner / G`` channels (the gate BEFORE the norm), times one
learned weight of ``d_inner``; ``W_out``.

``*``: GQA, ``num_attention_heads`` over ``num_key_value_heads`` of
``head_dim``, scores ``q . k / sqrt(head_dim)``, softmax in f32 over a causal
mask, NO rotary turn or other position. No cache.

``E``: ``s = sigmoid(u W_r)`` over all ``deployment.router_outputs``; the
``num_experts_per_tok`` largest of ``s + expert_bias`` (``n_group`` 1: a
plain top-k); the gates ``s`` (WITHOUT the bias) at the chosen over their sum
+ 1e-20 (``norm_topk_prob``) times ``routed_scaling_factor``; each expert
UNGATED, ``down(relu(up(x))^2)`` (``mlp_hidden_act`` relu2); the output
``shared(u)`` (one expert of that form,
``moe_shared_expert_intermediate_size`` wide) plus the gated sum over the
chosen experts THAT ARE HELD HERE (``deployment.experts_held``: first and
count). What the absent experts would add is computed by nobody, here as in
the program, and the partial sum goes on to the next layer.

Departures, each without effect on the values: every held expert is computed
on ALL tokens and weighted by the token's gate for it (zero where it was not
chosen or is not held), 2 experts at a time, rematerialised; attention is
taken one head and one block of queries at a time, rematerialised; the scan
over positions is cut into blocks that are rematerialised in the backward
pass (the states of 2 x 8,192 positions are 69 GB); the convolution is four
shifted products; and ``answers`` computes in BLOCKS as
``reference_ling.py``'s does: a forward pass that keeps every layer's input
on the host, then layer by layer backwards ``jax.vjp`` of that one layer,
the head in blocks of positions.

The parameter tree has the program's layout (``nemotron_h_init``) so that
both sides can be given the same seeded weights: ``embed`` [V,D],
``lm_head`` [D,V], ``final_norm`` [D], ``expert_bias`` [E layers, router
outputs], and under ``layers`` one stack for every run of like layers
(``00_mamba`` [1,...], ``01_moe`` [1,...] ...). It shares no code with the
program; it reads the configuration file's keys.

Besides its answers it hands out its routing: per expert layer and token the
experts it chose, what each router was given (``router_in``), and ``p_kth``,
``p_next``: the k-th and (k+1)-th of ``s + expert_bias``.

As a script (a child of the ``bare_routed`` job, which may not touch JAX
while this holds the chip):

    python3 chipbench/reference_nemotron_h.py <config.json> <sample.json> <out.npz>
"""

import json
import sys

import jax
import jax.numpy as jnp
import numpy as np

EXPERTS_AT_ONCE = 2  # [experts, T, width] float32 is 122 MB an expert at 2 x 8,192
SCAN_BLOCK = 128  # positions of the recurrence between two kept states
QUERY_BLOCK = 2048  # queries whose scores are held at once
HEAD_BLOCK = 4096  # positions whose logits are held at once
GATE_EPS = 1e-20
KINDS = {"M": "mamba", "E": "moe", "*": "attn"}


def kinds(cfg):
    """"mamba" | "moe" | "attn" of every kept layer."""
    return [KINDS[c] for c in cfg["hybrid_override_pattern"]]


def where(cfg):
    """For every layer: (the name of its run's stack, its index in it):
    neighbours of a kind share a stack, an expert layer stands alone."""
    out, run, ks = [], -1, kinds(cfg)
    for i, kind in enumerate(ks):
        if i and kind == ks[i - 1] and kind != "moe":
            out.append((out[-1][0], out[-1][1] + 1))
        else:
            run += 1
            out.append((f"{run:02d}_{kind}", 0))
    return out


def expert_bias(seed, scale, layers, experts):
    """The buffer both sides are given (``recipe.expert_bias`` of the
    configuration file): [expert layers, router outputs] float32."""
    return scale * jax.random.normal(
        jax.random.PRNGKey(seed), (layers, experts), jnp.float32)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _conv_silu(x, taps, bias):
    k, T = taps.shape[0], x.shape[1]
    past = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return jax.nn.silu(sum(past[:, j:j + T] * taps[j] for j in range(k)) + bias)


def recurrence(x, dt, a, bm, cm):
    """x [B,T,H,P]; dt [B,T,H]; a [H]; bm, cm [B,T,G,N] -> y [B,T,H,P]: the
    state one position after another, every head reading its group's B and C."""
    B, T, H, P = x.shape
    per = H // bm.shape[2]

    def step(S, inp):  # S [B,H,P,N]
        x_t, dt_t, b_t, c_t = inp
        b_t, c_t = jnp.repeat(b_t, per, axis=1), jnp.repeat(c_t, per, axis=1)  # [B,H,N]
        S = jnp.exp(dt_t * a)[..., None, None] * S \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return S, jnp.einsum("bhpn,bhn->bhp", S, c_t)

    @jax.checkpoint
    def block(S, xs):
        return jax.lax.scan(step, S, xs)

    pad = -T % SCAN_BLOCK  # positions of dt = 0 change nothing
    xs = [jnp.pad(m, ((0, 0), (0, pad)) + ((0, 0),) * (m.ndim - 2)) for m in (x, dt, bm, cm)]
    xs = tuple(jnp.moveaxis(m, 1, 0).reshape((-1, SCAN_BLOCK) + m.shape[:1] + m.shape[2:])
               for m in xs)
    _, y = jax.lax.scan(block, jnp.zeros((B, H, P, bm.shape[-1]), jnp.float32), xs)
    return jnp.moveaxis(y.reshape((-1,) + y.shape[2:]), 0, 1)[:, :T]


def _mamba(u, w, cfg, dot):
    H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    G, N = cfg["n_groups"], cfg["ssm_state_size"]
    (B, T, _), di = u.shape, H * P
    z, xbc, dt = jnp.split(dot(u, w["in_proj"]), [di, 2 * di + 2 * G * N], axis=-1)
    xbc = _conv_silu(xbc, w["conv_w"], w["conv_b"])
    x = xbc[..., :di].reshape(B, T, H, P)
    bm = xbc[..., di:di + G * N].reshape(B, T, G, N)
    cm = xbc[..., di + G * N:].reshape(B, T, G, N)
    dt = jax.nn.softplus(dt + w["dt_bias"])
    y = recurrence(x, dt, -jnp.exp(w["A_log"]), bm, cm) + w["D"][:, None] * x
    gated = (y.reshape(B, T, di) * jax.nn.silu(z)).reshape(B, T, G, di // G)
    gated = gated * jax.lax.rsqrt(
        jnp.mean(gated * gated, axis=-1, keepdims=True) + cfg["layer_norm_epsilon"])
    return dot(gated.reshape(B, T, di) * w["gate_norm"], w["out_proj"])


def _attention(u, w, cfg, dot):
    hq, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    B, S = u.shape[:2]
    q = dot(u, w["wq"]).reshape(B, S, hq, hd)
    k = jnp.repeat(dot(u, w["wk"]).reshape(B, S, hkv, hd), hq // hkv, axis=2)
    v = jnp.repeat(dot(u, w["wv"]).reshape(B, S, hkv, hd), hq // hkv, axis=2)
    block = min(QUERY_BLOCK, S)
    pad = -S % block
    at = jnp.arange(S)

    @jax.checkpoint
    def block_of(q1, first, k1, v1):  # q1 [B,block,hd]; k1, v1 [B,S,hd]
        s = jnp.einsum("bqd,bkd->bqk", q1, k1) / np.sqrt(hd)
        seen = at[None, :] <= (first + jnp.arange(block))[:, None]
        return jnp.einsum("bqk,bkd->bqd",
                          jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1), v1)

    def head(qkv):  # one head: three of [B,S,hd]
        q1, k1, v1 = qkv
        blocks = jnp.moveaxis(
            jnp.pad(q1, ((0, 0), (0, pad), (0, 0))).reshape(B, -1, block, hd), 1, 0)
        firsts = jnp.arange(blocks.shape[0]) * block
        o = jax.lax.map(lambda x: block_of(x[0], x[1], k1, v1), (blocks, firsts))
        return jnp.moveaxis(o, 0, 1).reshape(B, -1, hd)[:, :S]

    a = jax.lax.map(head, tuple(jnp.moveaxis(m, 2, 0) for m in (q, k, v)))  # [hq,B,S,hd]
    return dot(jnp.moveaxis(a, 0, 2).reshape(B, S, hq * hd), w["wo"])


def _relu2(x, wu, wd, dot):
    return dot(jnp.square(jax.nn.relu(dot(x, wu))), wd)


def _experts(x, weight_of, w, dot):
    """x [T,D]; weight_of [T,held]: a token's gate for each held expert,
    zero where it was not chosen -> sum over the held experts of gate *
    expert(x). Every held expert on every token, ``EXPERTS_AT_ONCE`` a time,
    rematerialised."""
    @jax.checkpoint
    def some(x, wu, wd, g):  # wu [e,D,W]; wd [e,W,D]; g [T,e]
        h = jnp.square(jax.nn.relu(dot(x, wu)))  # [e,T,W]
        return jnp.sum(jnp.swapaxes(g, 0, 1)[..., None] * dot(h, wd), axis=0)

    y = jnp.zeros_like(x)
    for e in range(0, w["w_up"].shape[0], EXPERTS_AT_ONCE):
        at = slice(e, e + EXPERTS_AT_ONCE)
        y = y + some(x, w["w_up"][at], w["w_down"][at], weight_of[:, at])
    return y


def choose(scores, bias, cfg):
    """scores [T,E] (sigmoid, float32), bias [E] -> (the experts chosen
    [T,k], their gates [T,k], ``p_kth``, ``p_next`` [T] of scores + bias)."""
    k = cfg["num_experts_per_tok"]
    top_p, top_i = jax.lax.top_k(scores + bias, k + 1)
    idx = top_i[:, :k]
    gates = jnp.take_along_axis(scores, idx, axis=-1)  # without the bias
    if cfg["norm_topk_prob"]:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + GATE_EPS)
    return (idx.astype(jnp.int32), gates * cfg["routed_scaling_factor"],
            top_p[:, k - 1], top_p[:, k])


def _routed(x, w, bias, cfg, dot, router_dot):
    """x [T,D] -> (the held experts' part of the layer's output plus the
    shared expert [T,D], its routing)."""
    first, held = cfg["deployment"]["experts_held"]
    scores = jax.nn.sigmoid(router_dot(x, w["router"]))  # [T, router outputs]
    idx, gates, p_k, p_n = choose(scores, bias, cfg)
    local = idx - first  # an absent expert's column is out of range: all zeros
    weight_of = jnp.sum(jax.nn.one_hot(local, held) * gates[..., None], axis=1)
    routing = {"routing": idx, "p_kth": p_k, "p_next": p_n, "router_in": x}
    y = _experts(x, weight_of, w, dot) + _relu2(x, w["shared_up"], w["shared_down"], dot)
    return y, jax.lax.stop_gradient(routing)


def layer(kind, w, bias, h, cfg, dot=jnp.matmul, router_dot=jnp.matmul):
    """One layer, ``w`` its own weights (no leading axis), ``bias`` [E] its
    row of ``expert_bias`` (None unless an expert layer) -> (h, its routing
    or None)."""
    u = _rmsnorm(h, w["norm"], cfg["layer_norm_epsilon"])
    if kind == "mamba":
        return h + _mamba(u, w, cfg, dot), None
    if kind == "attn":
        return h + _attention(u, w, cfg, dot), None
    y, routing = _routed(u.reshape(-1, u.shape[-1]), w, bias, cfg, dot, router_dot)
    return h + y.reshape(h.shape), routing


def _weights(params, cfg, i):
    """Layer ``i``'s weights in float32 and where they stand: (the name of
    its run's stack, its index in that stack, the weights, its bias row)."""
    name, at = where(cfg)[i]
    w = {k: v[at].astype(jnp.float32) for k, v in params["layers"][name].items()}
    ks = kinds(cfg)
    bias = params["expert_bias"][ks[:i].count("moe")] if ks[i] == "moe" else None
    return name, at, w, bias


def _logits(lm_head, final_norm, h, cfg, dot):
    return dot(_rmsnorm(h, final_norm, cfg["layer_norm_epsilon"]), lm_head)


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

    def one(kind, w, bias, h):
        return layer(kind, w, bias, h, cfg, **dots)

    def backwards(kind, w, bias, h, dh):
        _, back, _ = jax.vjp(lambda w, h: one(kind, w, bias, h), w, h, has_aux=True)
        dw, dh = back(dh)
        return dw, dh, sum(jnp.sum(jnp.square(g)) for g in jax.tree_util.tree_leaves(dw))

    forwards, backwards = jax.jit(one, static_argnums=0), jax.jit(backwards, static_argnums=0)
    with jax.default_matmul_precision("highest"):
        embed = params["embed"].astype(jnp.float32)
        h, inputs, routed = embed[tokens], [], []
        for i, kind in enumerate(ks):  # the layers' inputs wait on the host
            _, _, w, bias = _weights(params, cfg, i)
            inputs.append(np.asarray(h))
            h, r = forwards(kind, w, bias, h)
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
            dw, dh, sq = backwards(ks[i], w, bias, jnp.asarray(inputs.pop()), dh)
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
        sys.exit(f"chipbench/reference_nemotron_h.py: no TPU ({jax.devices()[0].platform})")
    tokens, positions = check_sample(cfg, sample, cfg["recipe"]["seq_len"])
    # the program's own (bf16-rounded) trainable weights stay as they are, 2
    # bytes a parameter; ``answers`` upcasts one layer at a time
    params = jax.jit(lambda: init_(jax.random.PRNGKey(sample["seed"]), pc))()
    params["expert_bias"] = expert_bias(
        **cfg["recipe"]["expert_bias"], experts=cfg["deployment"]["router_outputs"],
        layers=kinds(cfg).count("moe"))
    np.savez(argv[2], platform=jax.devices()[0].platform,
             **answers(params, tokens, cfg, positions, sample))


if __name__ == "__main__":
    main(sys.argv[1:])
