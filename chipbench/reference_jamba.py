"""The plain reference of a Jamba decoder without routed experts
(ai21labs/AI21-Jamba2-3B): forward, loss and gradients in straightforward
float32 ``jax.numpy`` — no kernels, matmuls at "highest" precision (a TPU
runs f32 matmuls in bf16 passes otherwise). It follows ``transformers``'
``models/jamba/modeling_jamba.py``, slow path, as remembered:

every layer: ``h = x + mixer(rmsnorm(x))``, then ``h + swiglu(rmsnorm(h))``,
``swiglu(u) = down(silu(gate(u)) * up(u))``; final RMSNorm; the head is the
embedding transposed. Layer ``i`` mixes with attention where
``i % attn_layer_period == attn_layer_offset`` and with Mamba otherwise.
Attention: q, k, v without bias, NO rotary or other positions, the one
key/value head repeated for every query head, softmax in f32 over a causal
mask at ``1 / sqrt(head size)``, ``o_proj`` without bias. Mamba
(``d_inner = mamba_expand * hidden_size``)::

    x, z = split(in_proj(u));  x = silu(causal depthwise conv(x) + bias)
    dt, B, C = split(x_proj(x));  dt, B, C = rmsnorm(dt), rmsnorm(B), rmsnorm(C)
    dt = softplus(dt_proj(dt) + dt_bias);  A = -exp(A_log)
    h_t = exp(dt_t[:, None] * A) * h_{t-1} + (dt_t * x_t)[:, None] * B_t[None, :]
    y_t = h_t @ C_t + D * x_t;  out = out_proj(y * silu(z))

with the recurrence as a ``lax.scan`` over positions, one after another.

What is done for memory and changes no value, because the cell's size needs
it (float32 weights are 6.4 GB and so are their gradients; one layer's
states ``[8192, 5120, 16]`` are 2.7 GB, and a backward pass wants three such
arrays): the scan over positions is cut into stretches of ``SCAN_STRETCH``
whose inner states are rematerialised in the backward pass; attention is
taken one query head at a time, rematerialised; and ``answers`` computes in
BLOCKS: a forward pass that keeps every layer's input, then layer by layer
backwards ``jax.vjp`` of that one layer, its gradient reduced at once to
its share of the squared norm and to the sampled leaves, the weights upcast
from the program's bf16 one layer at a time. ``forward`` is the same
equations all at once; the tests hold the two to each other.

The parameter tree has the program's layout (``jamba_init``) so that both
sides can be given the same seeded weights: ``embed`` [V,D], ``final_norm``
[D], and under ``layers`` one stack for every run of like layers, named by
its place and kind (``00_mamba`` [7,...], ``01_attn`` [1,...], ``02_mamba``
[6,...] for one period; conv_w [L,k,d_inner] with ``conv_w[:, k-1]`` on the
current position; A_log [L,d_inner,d_state]). It shares no code with
the program; it reads the configuration file's Hugging Face keys.

As a script (a child of the ``bare`` job, which may not touch JAX while this
holds the chip):

    python3 chipbench/reference_jamba.py <config.json> <sample.json> <out.npz>
"""

import json
import sys

import jax
import jax.numpy as jnp
import numpy as np

SCAN_STRETCH = 256


def kinds(cfg):
    """``JambaConfig.layers_block_type``."""
    return ["attention" if i % cfg["attn_layer_period"] == cfg["attn_layer_offset"]
            else "mamba" for i in range(cfg["num_hidden_layers"])]


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _recurrence(x, dt, A, B, C, state_dtype=jnp.float32, reset_every=None):
    """x, dt [T,d]; A [d,n]; B, C [T,n] -> y [T,d], position after position.
    ``state_dtype`` and ``reset_every`` are the tests' controls: a state
    kept in a lower precision, and one zeroed every so many positions (a
    lost carry), both of which the check has to refuse."""
    T = x.shape[0]

    def step(h, inp):
        x_t, dt_t, b_t, c_t, t = inp
        if reset_every:
            h = jnp.where(t % reset_every == 0, 0.0, h)
        decay = jnp.exp(dt_t[:, None] * A).astype(state_dtype)
        h = (decay * h + ((dt_t * x_t)[:, None] * b_t[None, :]).astype(state_dtype)
             ).astype(state_dtype)
        return h, h.astype(jnp.float32) @ c_t

    @jax.checkpoint
    def stretch(h, inp):
        return jax.lax.scan(step, h, inp)

    pad = -T % SCAN_STRETCH if T > SCAN_STRETCH else 0
    seq = (x, dt, B, C, jnp.arange(T))
    if pad:  # steps of size zero leave the state as it is
        seq = tuple(jnp.pad(m, ((0, pad),) + ((0, 0),) * (m.ndim - 1)) for m in seq)
    n = max(1, (T + pad) // SCAN_STRETCH)
    seq = tuple(m.reshape((n, -1) + m.shape[1:]) for m in seq)
    _, y = jax.lax.scan(stretch, jnp.zeros(A.shape, state_dtype), seq)
    return y.reshape(-1, x.shape[1])[:T]


def _mamba(u, w, cfg, dot, **controls):
    n, r, k = cfg["mamba_d_state"], cfg["mamba_dt_rank"], cfg["mamba_d_conv"]
    eps, T = cfg["rms_norm_eps"], u.shape[1]
    xz = dot(u, w["in_proj"])
    x, z = xz[..., : xz.shape[-1] // 2], xz[..., xz.shape[-1] // 2:]
    past = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    x = sum(past[:, j:j + T] * w["conv_w"][j] for j in range(k))
    x = jax.nn.silu(x + w["conv_b"] if "conv_b" in w else x)
    dbc = dot(x, w["x_proj"])
    dt = _rmsnorm(dbc[..., :r], w["dt_norm"], eps)
    B = _rmsnorm(dbc[..., r:r + n], w["b_norm"], eps)
    C = _rmsnorm(dbc[..., r + n:], w["c_norm"], eps)
    dt = jax.nn.softplus(dot(dt, w["dt_proj"]) + w["dt_bias"])
    A = -jnp.exp(w["A_log"])
    y = jnp.stack([_recurrence(x[b], dt[b], A, B[b], C[b], **controls)
                   for b in range(u.shape[0])])
    return dot((y + w["D"] * x) * jax.nn.silu(z), w["out_proj"])


def _attention(u, w, cfg, dot):
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // hq
    B, S = u.shape[:2]
    q = dot(u, w["wq"]).reshape(B, S, hq, hd)
    k = jnp.repeat(dot(u, w["wk"]).reshape(B, S, hkv, hd), hq // hkv, axis=2)
    v = jnp.repeat(dot(u, w["wv"]).reshape(B, S, hkv, hd), hq // hkv, axis=2)
    causal = jnp.tril(jnp.ones((S, S), bool))

    @jax.checkpoint
    def head(qkv):  # [B,S,hd] each: explicit masked softmax, no positions
        q1, k1, v1 = qkv
        s = jnp.einsum("bqd,bkd->bqk", q1, k1) / np.sqrt(hd)
        return jnp.einsum("bqk,bkd->bqd",
                          jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1), v1)

    a = jax.lax.map(head, tuple(jnp.moveaxis(m, 2, 0) for m in (q, k, v)))
    return dot(jnp.moveaxis(a, 0, 2).reshape(B, S, hq * hd), w["wo"])


def layer(kind, w, h, cfg, dot=jnp.matmul, **controls):
    """One layer, ``w`` its own weights (no leading axis)."""
    eps = cfg["rms_norm_eps"]
    u = _rmsnorm(h, w["norm"], eps)
    h = h + (_mamba(u, w, cfg, dot, **controls) if kind == "mamba"
             else _attention(u, w, cfg, dot))
    u = _rmsnorm(h, w["ffn_norm"], eps)
    return h + dot(jax.nn.silu(dot(u, w["w_gate"])) * dot(u, w["w_up"]), w["w_down"])


def _weights(params, kinds_, i):
    """Layer ``i``'s weights in float32 and where they stand: (the name of
    its run's stack, its index in that stack, the weights)."""
    first = i
    while first and kinds_[first - 1] == kinds_[i]:
        first -= 1
    run = sum(1 for j in range(1, first + 1) if kinds_[j] != kinds_[j - 1])
    name = f"{run:02d}_" + ("mamba" if kinds_[i] == "mamba" else "attn")
    return name, i - first, {k: v[i - first].astype(jnp.float32)
                             for k, v in params["layers"][name].items()}


def _logits(params, h, cfg, dot):
    embed = params["embed"].astype(jnp.float32)
    head = embed.T if cfg["tie_word_embeddings"] else params["lm_head"].astype(jnp.float32)
    return dot(_rmsnorm(h, params["final_norm"].astype(jnp.float32),
                        cfg["rms_norm_eps"]), head)


def forward(params, tokens, cfg, dot=jnp.matmul, **controls):
    """tokens int [B,S] -> logits f32 [B,S,V], all at once. ``dot``
    multiplies activations by a weight matrix; the tests pass one of a lower
    precision, or the recurrence's ``controls``, to show that the check
    refuses them."""
    ks = kinds(cfg)
    h = params["embed"].astype(jnp.float32)[tokens]
    for i, kind in enumerate(ks):
        h = layer(kind, _weights(params, ks, i)[2], h, cfg, dot, **controls)
    return _logits(params, h, cfg, dot)


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


def grad_answers(grads, sample):
    """Both sides' gradients as the check compares them: the global norm,
    and of each leaf named in ``sample["grad_leaves"]`` (a path in the
    parameter tree, "layers.00_mamba.x_proj": all layers of that stack) every
    k-th element."""
    out = {"grad_norm": jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                                     for g in jax.tree_util.tree_leaves(grads)))}
    for path in sample["grad_leaves"]:
        g = grads
        for key in path.split("."):
            g = g[key]
        out["grad." + path] = _sampled(g.reshape(-1), g.size, sample["grad_elements"])
    return out


def answers(params, tokens, cfg, positions, sample, dot=jnp.matmul, **controls):
    """What the check compares: logits at ``positions`` of every sequence,
    the loss (targets = tokens, as the trainer feeds them), the global
    gradient norm and the sampled gradient leaves: in blocks (see the
    module's text). ``params`` in any dtype; computed in f32."""
    ks = kinds(cfg)
    wanted = {p: {} for p in sample["grad_leaves"]}  # path -> {layer: elements}

    def keep(path, leaf, g, at=0):
        if path in wanted:
            wanted[path][at] = _sampled(g.reshape(-1), leaf.size,
                                        sample["grad_elements"], at * g.size)

    @jax.jit
    def head(embed, final_norm, h):
        def f(embed, final_norm, h):
            logits = _logits({"embed": embed, "final_norm": final_norm}, h, cfg, dot)
            return loss(logits, tokens), logits[:, positions]

        val, back, logits = jax.vjp(f, embed, final_norm, h, has_aux=True)
        return (val, logits) + back(jnp.ones((), jnp.float32))

    def backwards(kind, w, h, dh):
        _, back = jax.vjp(lambda w, h: layer(kind, w, h, cfg, dot, **controls), w, h)
        dw, dh = back(dh)
        return dw, dh, sum(jnp.sum(jnp.square(g)) for g in jax.tree_util.tree_leaves(dw))

    backwards = jax.jit(backwards, static_argnums=0)
    forwards = jax.jit(lambda kind, w, h: layer(kind, w, h, cfg, dot, **controls),
                       static_argnums=0)
    if cfg["tie_word_embeddings"] is not True:
        raise ValueError("reference_jamba.answers: the tied head only")
    with jax.default_matmul_precision("highest"):
        embed = params["embed"].astype(jnp.float32)
        inputs = [embed[tokens]]
        for i, kind in enumerate(ks):
            inputs.append(forwards(kind, _weights(params, ks, i)[2], inputs[-1]))
        val, logits, d_embed, d_norm, dh = head(
            embed, params["final_norm"].astype(jnp.float32), inputs.pop())
        squares = jnp.sum(jnp.square(d_norm))
        keep("final_norm", d_norm, d_norm)
        for i in reversed(range(len(ks))):
            name, at, w = _weights(params, ks, i)
            dw, dh, sq = backwards(ks[i], w, inputs.pop(), dh)
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
               for p, got in wanted.items()}}


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
        sys.exit(f"chipbench/reference_jamba.py: no TPU ({jax.devices()[0].platform})")
    tokens, positions = check_sample(cfg, sample, cfg["recipe"]["seq_len"])
    # the program's own (bf16-rounded) weights stay as they are, 2 bytes a
    # parameter; ``answers`` upcasts one layer at a time
    params = jax.jit(lambda: init_(jax.random.PRNGKey(sample["seed"]), pc))()
    np.savez(argv[2], platform=jax.devices()[0].platform,
             **answers(params, tokens, cfg, positions, sample))


if __name__ == "__main__":
    main(sys.argv[1:])
