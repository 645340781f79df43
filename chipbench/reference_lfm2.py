"""The plain reference of LiquidAI's LFM2 mixture-of-experts decoder
(LiquidAI/LFM2-8B-A1B, ``model_type`` ``lfm2_moe``): forward, loss and
gradients in straightforward float32 ``jax.numpy`` — no kernels, no sort, no
grouped product, no scan over stacks, matmuls at "highest" precision (a TPU
runs f32 matmuls in bf16 passes otherwise). It follows ``transformers``'
``models/lfm2_moe/modeling_lfm2_moe.py`` as remembered (there is no network
here), with ``n(.)`` an RMSNorm of ``norm_eps`` and a learned weight:

every layer: ``h = h + mixer(n_op(h))``, then ``h = h + ffn(n_ffn(h))``;
layer ``i`` mixes as ``layer_types[i]`` says and has a dense SwiGLU where
``i < num_dense_layers``, routed experts otherwise; final RMSNorm; the head
is the embedding transposed.

``conv``: ``B, C, X = split3(in_proj(u))``; ``c[t] = k0*(B*X)[t-2] +
k1*(B*X)[t-1] + k2*(B*X)[t]`` (depthwise, causal, ``conv_L_cache`` taps a
channel, no bias); ``out_proj(C * c)``. ``full_attention``: q, k, v without
bias, an RMSNorm over each head's values of q and of k (``q_layernorm``,
``k_layernorm``, one weight of the head's size each), ``rotate_half`` rotary,
each key/value head repeated for its query heads, softmax in f32 over a
causal mask at ``1 / sqrt(head size)``, ``out_proj`` without bias. Dense
feed-forward: ``w2(silu(w1 z) * w3 z)``. Expert feed-forward: ``s =
sigmoid(z W_r)`` over all experts; the chosen are ``top_k(s + expert_bias)``;
their gates are ``s`` at the chosen, WITHOUT the bias, over ``their sum +
1e-6`` (``norm_topk_prob``), times ``routed_scaling_factor``; the output is
the gated sum of the chosen experts' SwiGLUs. No shared expert, no auxiliary
loss. ``expert_bias`` is a buffer of the published model: it is given, takes
no gradient and is not among the gradients compared.

Departures from that file, each without effect on the values: every expert
is computed on ALL tokens and weighted by the token's gate for that expert
(zero where it was not chosen), where the published code gathers each
expert's tokens (the same sum; a masked dense product holds no index
arithmetic that could share a fault with the program's sort), 8 experts at a
time, rematerialised in the backward pass; attention is taken one query head
at a time, rematerialised; the convolution is three shifted products and no
``conv1d``; no attention mask or padding (the sample has none); and
``answers`` computes in BLOCKS, because float32 weights are 6.7 GB and so are
their gradients: a forward pass that keeps every layer's input, then layer by
layer backwards ``jax.vjp`` of that one layer, its gradient reduced at once
to its share of the squared norm and to the sampled leaves, the weights
upcast from the program's bf16 one layer at a time. ``forward`` is the same
equations all at once; the tests hold the two to each other.

The parameter tree has the program's layout (``lfm2_init``) so that both
sides can be given the same seeded weights: ``embed`` [V,D], ``final_norm``
[D], ``expert_bias`` [expert layers, E], and under ``layers`` one stack for
every run of like layers, named by its place and kinds (``00_conv_dense``
[1,...], ``01_attn_moe`` [1,...], ``02_conv_moe`` [1,...] ...: dense layers
of one mixer run together, an expert layer alone; conv_w [L,k,D] with
``conv_w[:, k-1]`` on the current position; experts w_gate / w_up [L,E,D,H],
w_down [L,E,H,D], router [L,D,E]). It shares no code with the program; it
reads the configuration file's Hugging Face keys.

Besides its answers it hands out its routing (per expert layer and token the
k experts it chose and ``s + expert_bias`` of its k-th and (k+1)-th choice,
which is what decided) and what each router was given (``router_in``).

As a script (a child of the ``bare_routed`` job, which may not touch JAX
while this holds the chip):

    python3 chipbench/reference_lfm2.py <config.json> <sample.json> <out.npz>
"""

import json
import sys

import jax
import jax.numpy as jnp
import numpy as np

EXPERTS_AT_ONCE = 8
GATE_EPS = 1e-6


def kinds(cfg):
    """(mixer, feed-forward) of every layer."""
    return [("attn" if t == "full_attention" else "conv",
             "dense" if i < cfg["num_dense_layers"] else "moe")
            for i, t in enumerate(cfg["layer_types"])]


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
    configuration file: a seed and a scale; the program's trainer draws its
    own from its key): [expert layers, experts] float32."""
    return scale * jax.random.normal(
        jax.random.PRNGKey(seed), (layers, experts), jnp.float32)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rotary(x, theta):
    # x [B,S,H,hd]; HF rotate_half: pairs are (i, i + hd/2)
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv  # [S,hd/2]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _conv(u, w, cfg, dot):
    k, T = cfg["conv_L_cache"], u.shape[1]
    bcx = dot(u, w["in_proj"])
    d = bcx.shape[-1] // 3
    b, c, x = bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
    past = jnp.pad(b * x, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(past[:, j:j + T] * w["conv_w"][j] for j in range(k))
    return dot(c * conv, w["out_proj"])


def _attention(u, w, cfg, dot):
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, eps = cfg["hidden_size"] // hq, cfg["norm_eps"]
    B, S = u.shape[:2]
    q = _rmsnorm(dot(u, w["wq"]).reshape(B, S, hq, hd), w["q_norm"], eps)
    k = _rmsnorm(dot(u, w["wk"]).reshape(B, S, hkv, hd), w["k_norm"], eps)
    q, k = _rotary(q, cfg["rope_theta"]), _rotary(k, cfg["rope_theta"])
    k = jnp.repeat(k, hq // hkv, axis=2)  # query head j reads kv head j // g
    v = jnp.repeat(dot(u, w["wv"]).reshape(B, S, hkv, hd), hq // hkv, axis=2)
    causal = jnp.tril(jnp.ones((S, S), bool))

    @jax.checkpoint
    def head(qkv):  # [B,S,hd] each: explicit masked softmax
        q1, k1, v1 = qkv
        s = jnp.einsum("bqd,bkd->bqk", q1, k1) / np.sqrt(hd)
        return jnp.einsum("bqk,bkd->bqd",
                          jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1), v1)

    a = jax.lax.map(head, tuple(jnp.moveaxis(m, 2, 0) for m in (q, k, v)))
    return dot(jnp.moveaxis(a, 0, 2).reshape(B, S, hq * hd), w["wo"])


def _experts(x, weight_of, w, dot):
    """x [T,D]; weight_of [T,E]: a token's gate for each expert, zero where
    the expert was not chosen -> sum over experts of gate * expert(x). Every
    expert on every token, ``EXPERTS_AT_ONCE`` a time, rematerialised."""
    @jax.checkpoint
    def some(x, wg, wu, wd, g):  # wg, wu [e,D,H]; wd [e,H,D]; g [T,e]
        h = jax.nn.silu(dot(x, wg)) * dot(x, wu)  # [e,T,H]
        return jnp.sum(jnp.swapaxes(g, 0, 1)[..., None] * dot(h, wd), axis=0)

    y = jnp.zeros_like(x)
    for e in range(0, w["w_gate"].shape[0], EXPERTS_AT_ONCE):
        at = slice(e, e + EXPERTS_AT_ONCE)
        y = y + some(x, w["w_gate"][at], w["w_up"][at], w["w_down"][at], weight_of[:, at])
    return y


def _routed(x, w, bias, cfg, dot, router_dot):
    """x [T,D] -> (the expert layer's output [T,D], its routing)."""
    E, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    scores = jax.nn.sigmoid(router_dot(x, w["router"]))  # [T,E]
    decide = scores + bias if cfg["use_expert_bias"] else scores
    top_p, top_i = jax.lax.top_k(decide, min(k + 1, E))
    idx = top_i[:, :k]
    gates = jnp.take_along_axis(scores, idx, axis=-1)  # without the bias
    if cfg["norm_topk_prob"]:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + GATE_EPS)
    gates = gates * cfg["routed_scaling_factor"]
    weight_of = jnp.sum(jax.nn.one_hot(idx, E) * gates[..., None], axis=1)
    routing = {"routing": idx.astype(jnp.int32), "p_kth": top_p[:, k - 1],
               "p_next": top_p[:, -1], "router_in": x}
    return _experts(x, weight_of, w, dot), jax.lax.stop_gradient(routing)


def layer(kind, w, bias, h, cfg, dot=jnp.matmul, router_dot=jnp.matmul):
    """One layer, ``w`` its own weights (no leading axis), ``bias`` [E] its
    row of ``expert_bias`` (None for a dense layer) -> (h, its routing or
    None)."""
    eps = cfg["norm_eps"]
    u = _rmsnorm(h, w["norm"], eps)
    h = h + (_conv(u, w, cfg, dot) if kind[0] == "conv" else _attention(u, w, cfg, dot))
    z = _rmsnorm(h, w["ffn_norm"], eps)
    if kind[1] == "dense":
        return h + dot(jax.nn.silu(dot(z, w["w_gate"])) * dot(z, w["w_up"]),
                       w["w_down"]), None
    y, routing = _routed(z.reshape(-1, z.shape[-1]), w, bias, cfg, dot, router_dot)
    return h + y.reshape(h.shape), routing


def _weights(params, cfg, i):
    """Layer ``i``'s weights in float32 and where they stand: (the name of
    its run's stack, its index in that stack, the weights, its bias row)."""
    name, at = where(cfg)[i]
    w = {k: v[at].astype(jnp.float32) for k, v in params["layers"][name].items()}
    moe = i - cfg["num_dense_layers"]
    bias = params["expert_bias"][moe] if moe >= 0 and "expert_bias" in params else None
    return name, at, w, bias


def _logits(embed, final_norm, h, cfg, dot):
    return dot(_rmsnorm(h, final_norm, cfg["norm_eps"]), embed.T)


def forward(params, tokens, cfg, **dots):
    """tokens int [B,S] -> (logits f32 [B,S,V], the expert layers' routing,
    each stacked over them), all at once. ``dot`` multiplies activations by
    a weight matrix, ``router_dot`` by a router's; the tests pass ones of a
    lower precision to show that the check refuses them."""
    embed = params["embed"].astype(jnp.float32)
    h, routed = embed[tokens], []
    for i, kind in enumerate(kinds(cfg)):
        _, _, w, bias = _weights(params, cfg, i)
        h, r = layer(kind, w, bias, h, cfg, **dots)
        if r is not None:
            routed.append(r)
    logits = _logits(embed, params["final_norm"].astype(jnp.float32), h, cfg,
                     dots.get("dot", jnp.matmul))
    return logits, {k: jnp.stack([r[k] for r in routed]) for k in routed[0]}


def loss(logits, targets):
    """Mean cross-entropy of logits[b, s] against targets[b, s]."""
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
    each expert's matrix less the layer's mean over experts, flattened. A
    gate is a factor of its expert's whole gradient: gates computed from the
    wrong array (the biased scores) move these norms expert by expert, by
    the bias over the score, where rounding, which is alike for every
    expert, leaves them be; element by element the same fault is a percent
    under four percent of rounding."""
    n = jnp.sqrt(jnp.sum(jnp.square(g.astype(jnp.float32)), axis=(-2, -1)))
    return (n - jnp.mean(n, axis=-1, keepdims=True)).reshape(-1)


def grad_answers(grads, sample):
    """Both sides' gradients as the check compares them: the global norm,
    and of each leaf named in ``sample["grad_leaves"]`` (a path in the
    parameter tree, "layers.02_conv_moe.w_down": all layers of that stack)
    every k-th element, or, with ``@expert_norms`` behind the path,
    :func:`_expert_norms` of it."""
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
    def head(embed, final_norm, h):
        def f(embed, final_norm, h):
            logits = _logits(embed, final_norm, h, cfg, dot)
            return loss(logits, tokens), logits[:, positions]

        val, back, logits = jax.vjp(f, embed, final_norm, h, has_aux=True)
        return (val, logits) + back(jnp.ones((), jnp.float32))

    def backwards(kind, w, bias, h, dh):
        _, back, _ = jax.vjp(lambda w, h: layer(kind, w, bias, h, cfg, **dots), w, h,
                             has_aux=True)
        dw, dh = back(dh)
        return dw, dh, sum(jnp.sum(jnp.square(g)) for g in jax.tree_util.tree_leaves(dw))

    backwards = jax.jit(backwards, static_argnums=0)
    forwards = jax.jit(lambda kind, w, bias, h: layer(kind, w, bias, h, cfg, **dots),
                       static_argnums=0)
    with jax.default_matmul_precision("highest"):
        embed = params["embed"].astype(jnp.float32)
        inputs, routed = [embed[tokens]], []
        for i, kind in enumerate(ks):
            _, _, w, bias = _weights(params, cfg, i)
            h, r = forwards(kind, w, bias, inputs[-1])
            inputs.append(h)
            if r is not None:
                routed.append({k: np.asarray(v) for k, v in r.items()})
        val, logits, d_embed, d_norm, dh = head(
            embed, params["final_norm"].astype(jnp.float32), inputs.pop())
        squares = jnp.sum(jnp.square(d_norm))
        keep("final_norm", d_norm, d_norm)
        for i in reversed(range(len(ks))):
            name, at, w, bias = _weights(params, cfg, i)
            dw, dh, sq = backwards(ks[i], w, bias, inputs.pop(), dh)
            squares = squares + sq
            for key, g in dw.items():
                keep(f"layers.{name}.{key}", params["layers"][name][key], g, at)
            del dw
        d_embed = d_embed.at[tokens].add(dh)  # the one leaf's two uses, summed
        squares = squares + jnp.sum(jnp.square(d_embed))
        keep("embed", embed, d_embed)
    missing = [p for p, got in wanted.items() if not got]
    if missing:
        raise KeyError(f"no gradient leaf {missing}")
    return {"logits": np.asarray(logits), "loss": float(val),
            "grad_norm": np.asarray(jnp.sqrt(squares)),
            **{"grad." + p: np.concatenate([np.asarray(got[at]) for at in sorted(got)])
               for p, got in wanted.items()},
            **{k: np.stack([r[k] for r in routed]) for k in routed[0]}}


def check_sample(cfg, sample, seq):
    """The seeded sample both sides are run on (independent of --seed, so
    the reference's answers can be cached): tokens and sampled positions."""
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
        sys.exit(f"chipbench/reference_lfm2.py: no TPU ({jax.devices()[0].platform})")
    tokens, positions = check_sample(cfg, sample, cfg["recipe"]["seq_len"])
    # the program's own (bf16-rounded) trainable weights stay as they are, 2
    # bytes a parameter; ``answers`` upcasts one layer at a time
    params = jax.jit(lambda: init_(jax.random.PRNGKey(sample["seed"]), pc))()
    params["expert_bias"] = expert_bias(
        **cfg["recipe"]["expert_bias"], experts=cfg["num_experts"],
        layers=cfg["num_hidden_layers"] - cfg["num_dense_layers"])
    np.savez(argv[2], platform=jax.devices()[0].platform,
             **answers(params, tokens, cfg, positions, sample))


if __name__ == "__main__":
    main(sys.argv[1:])
