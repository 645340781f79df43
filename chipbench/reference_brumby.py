"""The plain reference of Manifest AI's Brumby (a dense decoder whose layers
mix positions by POWER RETENTION, arXiv:2507.04239): forward, loss and
gradients in straightforward float32 ``jax.numpy`` — no kernels, no state, no
chunks, no scan: the retention as the MASKED QUADRATIC FORM it is defined
by, Python loops over the layers, matmuls at "highest" precision (a TPU runs
f32 matmuls in bf16 passes otherwise). The family's modelling code could not
be read here (there is no network); the equations are those ISSUE 56 writes
out, each convention no key gives listed under ``assumed`` in the
configuration file. With ``n(x; w) = x / sqrt(mean(x^2) + eps) * w``, eps
``rms_norm_eps``, no bias but the gate's:

layer: ``x = n(h; attn_norm)``; ``q, k, v = x wq, x wk, x wv`` as heads of
``head_dim``; ``q = n(q; q_norm)``, ``k = n(k; k_norm)`` over each head, one
weight of ``head_dim`` each; rotary on all of ``head_dim``, halves rotated,
``rope_theta``, positions from 0; ``g = logsigmoid(x wg + bg)`` one a
key/value head, ``G`` its running sum over positions; for query head ``i``,
which reads key/value head ``j = i // (heads / kv heads)``::

    a[t, r] = exp(G_t[j] - G_r[j]) (head_dim^-1/2 q_t[i] . k_r[j])^2   for r <= t, else 0
    y_t[i]  = sum_r a[t, r] v_r[j] / (sum_r a[t, r] + 1e-6)

``h = h + y wo``; ``h = h + down(silu(gate(x')) * up(x'))``, ``x' = n(h;
ffn_norm)``. A final norm and an untied head; the loss is the mean
cross-entropy.

Departures, each without effect on the values: the retention is taken a
key/value head and ``ROWS`` query positions at a time against every key
under the mask, one block after the other (``lax.map``), each
rematerialised (five heads' float32 weights over 1,024 x 16,384 are 336 MB
and their backward three times that; 128 such blocks side by side were 20 GB);
the feed-forward ``ROWS_FFN`` positions at a time likewise; and ``answers``
computes in BLOCKS as ``reference_ouro.py``'s does: a forward pass that keeps every
layer's input, the loss in blocks of positions, then layer by layer backwards
``jax.vjp`` of that one layer, and of a layer's gradient only its share of
the global norm and the sampled elements are kept (1.5 billion float32
gradients beside as many weights are 12 GB).

The parameter tree has the program's layout (``brumby_init``) so that both
sides can be given the same seeded weights: ``embed`` [V,D], ``lm_head``
[D,V], ``final_norm`` [D], and a stack ``layers.<NN>_retention.*`` [1, ...]
a layer. It shares no code with the program; it reads the configuration
file's keys. What is no model's own (the sampled leaves, the seeded sample)
is ``reference.py``'s.

As a script (a child of the ``bare`` job, which may not touch JAX while this
holds the chip):

    python3 chipbench/reference_brumby.py <config.json> <sample.json> <out.npz>
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from chipbench.reference import check_sample, grad_answers  # noqa: E402,F401  (no model's own)

ROWS = 1024  # query positions whose weights over the keys are alive at once
ROWS_FFN = 4096  # positions whose feed-forward temporaries are alive at once
HEAD_BLOCK = 2048  # positions whose logits are alive at once
EPS_N = 1e-6  # added to the normaliser


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


def _rows(q, k, v, G_q, G_k, first):
    """One key/value head, a block of query positions: q [B,R,rep,hd]
    (scaled), k, v [B,S,hd], G_q [B,R], G_k [B,S] the running log-decays,
    ``first`` the block's first position -> (y [B,R,rep,hd], the smallest
    normaliser)."""
    R, S = q.shape[1], k.shape[1]
    s = jnp.einsum("bqhd,bkd->bhqk", q, k)
    seen = (jnp.arange(S)[None, :] <= first + jnp.arange(R)[:, None])  # r <= t
    decay = jnp.exp(jnp.where(seen, G_q[:, :, None] - G_k[:, None, :], -jnp.inf))
    a = decay[:, None] * s * s
    den = jnp.swapaxes(jnp.sum(a, axis=-1), 1, 2)  # [B,R,rep]
    y = jnp.einsum("bhqk,bkd->bqhd", a, v) / (den[..., None] + EPS_N)
    return y, jax.lax.stop_gradient(jnp.min(den))


def _in_blocks(x, rows):
    """[B,S,...] -> [S / rows, B, rows, ...]: blocks of positions first."""
    B, S = x.shape[:2]
    return jnp.moveaxis(x.reshape(B, S // rows, rows, *x.shape[2:]), 1, 0)


def _whole(blocks):
    """:func:`_in_blocks`' inverse."""
    x = jnp.moveaxis(blocks, 0, 1)
    return x.reshape(x.shape[0], -1, *x.shape[3:])


def retention(q, k, v, g):
    """q [B,S,Hq,hd]; k, v [B,S,H,hd]; g [B,S,H] -> (y [B,S,Hq,hd], the
    smallest normaliser): the masked quadratic form, a key/value head and a
    block of rows at a time, one after the other."""
    B, S, Hq, hd = q.shape
    H = k.shape[2]
    rep, G, rows = Hq // H, jnp.cumsum(g, axis=1), min(ROWS, S)
    q = q * hd ** -0.5  # inside the square
    heads, den_min = [], jnp.inf
    for j in range(H):
        block = jax.checkpoint(lambda xs, j=j: _rows(
            xs[0], k[:, :, j], v[:, :, j], xs[1], G[:, :, j], xs[2]))
        y, den = jax.lax.map(block, (
            _in_blocks(q[:, :, j * rep:(j + 1) * rep], rows), _in_blocks(G[:, :, j], rows),
            jnp.arange(0, S, rows)))
        heads.append(_whole(y))
        den_min = jnp.minimum(den_min, jnp.min(den))
    return jnp.concatenate(heads, axis=2), den_min


def _ffn(w, x, dot):
    def block(x):
        return dot(jax.nn.silu(dot(x, w["w_gate"])) * dot(x, w["w_up"]), w["w_down"])

    rows = min(ROWS_FFN, x.shape[1])
    return _whole(jax.lax.map(jax.checkpoint(block), _in_blocks(x, rows)))


def layer(w, h, cfg, dot=jnp.matmul):
    """One layer (the module's text); w: its leaves, float32 -> (h, the
    smallest normaliser)."""
    hq, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    B, S = h.shape[:2]
    x = _rmsnorm(h, w["attn_norm"], eps)
    q = _rotary(_rmsnorm(dot(x, w["wq"]).reshape(B, S, hq, hd), w["q_norm"], eps), theta)
    k = _rotary(_rmsnorm(dot(x, w["wk"]).reshape(B, S, hkv, hd), w["k_norm"], eps), theta)
    v = dot(x, w["wv"]).reshape(B, S, hkv, hd)
    g = jax.nn.log_sigmoid(dot(x, w["wg"]) + w["bg"])
    y, den_min = retention(q, k, v, g)
    h = h + dot(y.reshape(B, S, hq * hd), w["wo"])
    return h + _ffn(w, _rmsnorm(h, w["ffn_norm"], eps), dot), den_min


def stacks(cfg):
    """The names of the layers' stacks in the parameter tree, in order."""
    return [f"{i:02d}_retention" for i in range(cfg["num_hidden_layers"])]


def _weights(params, name):
    """One layer's leaves out of its stack of one, float32."""
    return jax.tree_util.tree_map(lambda x: x[0].astype(jnp.float32), params["layers"][name])


def hidden(params, tokens, cfg, dot=jnp.matmul):
    h = params["embed"].astype(jnp.float32)[tokens]
    for name in stacks(cfg):
        h, _ = layer(_weights(params, name), h, cfg, dot)
    return _rmsnorm(h, params["final_norm"].astype(jnp.float32), cfg["rms_norm_eps"])


def forward(params, tokens, cfg, dot=jnp.matmul):
    """tokens int [B,S] -> logits f32 [B,S,V], whole (the tests' sizes)."""
    return dot(hidden(params, tokens, cfg, dot), params["lm_head"].astype(jnp.float32))


def loss(logits, targets):
    """Mean cross-entropy of logits[b, s] against targets[b, s]."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))


def answers(params, tokens, cfg, positions, sample, dot=jnp.matmul):
    """What the check compares: logits at ``positions`` of every sequence,
    the loss (targets = tokens, as the trainer feeds them), the global
    gradient norm and the sampled gradient leaves, and ``den_min`` (the
    smallest normaliser of any layer, head and position); in blocks (the
    module's text). ``params`` in any dtype; computed in f32."""
    eps, n_tokens, names = cfg["rms_norm_eps"], tokens.size, stacks(cfg)
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    wanted = {p: p.split(".") for p in sample["grad_leaves"]}

    def keep(out, prefix, grads):
        """Of ``grads`` (a dict of leaves under ``prefix``): their squares'
        sum and the sampled elements, as ``grad_answers`` takes them."""
        out["sq"] = out.get("sq", 0.0) + sum(jnp.sum(jnp.square(g)) for g in grads.values())
        for path, keys in wanted.items():
            if keys[:-1] == prefix and keys[-1] in grads:
                g = grads[keys[-1]]
                every = -(-g.size // sample["grad_elements"])
                out["grad." + path] = g.reshape(-1)[::every]

    step = jax.jit(lambda w, h: layer(w, h, cfg, dot))

    @jax.jit
    def layer_back(w, h, dh):
        _, pull = jax.vjp(lambda w, h: layer(w, h, cfg, dot)[0], w, h)
        return pull(dh)

    @jax.jit
    def head_block(final_norm, lm_head, h, targets):  # a block of positions: sums
        def f(final_norm, lm_head, h):
            logp = jax.nn.log_softmax(dot(_rmsnorm(h, final_norm, eps), lm_head), axis=-1)
            return -jnp.sum(jnp.take_along_axis(logp, targets[..., None], axis=-1)) / n_tokens
        val, pull = jax.vjp(f, final_norm, lm_head, h)
        return (val, *pull(jnp.ones((), jnp.float32)))

    out = {}
    with jax.default_matmul_precision("highest"):
        final_norm, lm_head = f32(params["final_norm"]), f32(params["lm_head"])
        h, kept, den_min = f32(params["embed"])[tokens], [], np.inf
        for name in names:
            kept.append(h)
            h, den = step(_weights(params, name), h)
            den_min = min(den_min, float(den))
        logits = dot(_rmsnorm(h[:, positions], final_norm, eps), lm_head)
        val, d_norm, d_head, d_h = 0.0, 0.0, 0.0, []
        for s in range(0, tokens.shape[1], HEAD_BLOCK):
            v, dn, dl, dh = head_block(final_norm, lm_head, h[:, s:s + HEAD_BLOCK],
                                       tokens[:, s:s + HEAD_BLOCK])
            val, d_norm, d_head, d_h = val + v, d_norm + dn, d_head + dl, d_h + [dh]
        keep(out, [], {"final_norm": d_norm, "lm_head": d_head})
        del d_head, lm_head
        dh = jnp.concatenate(d_h, axis=1)
        for name in reversed(names):
            dw, dh = layer_back(_weights(params, name), kept.pop(), dh)
            keep(out, ["layers", name], jax.tree_util.tree_map(lambda x: x[None], dw))
            del dw
        keep(out, [], {"embed": jnp.zeros(params["embed"].shape, jnp.float32).at[tokens].add(dh)})
    sq = out.pop("sq")
    return {"logits": np.asarray(logits), "loss": float(val), "grad_norm": float(jnp.sqrt(sq)),
            "den_min": den_min, **{k: np.asarray(v) for k, v in out.items()}}


def main(argv):
    from chipbench import manifest

    if jax.devices()[0].platform != "tpu":  # before any work: no CPU answers
        sys.exit(f"chipbench/reference_brumby.py: no TPU ({jax.devices()[0].platform})")
    with open(argv[0]) as f, open(argv[1]) as g:
        cfg, sample = json.load(f), json.load(g)
    adapter = manifest.adapter_for(argv[0], cfg)  # the program's init, for equal weights
    tokens, positions = check_sample(cfg, sample, cfg["recipe"]["seq_len"])
    # the program's bf16-rounded weights as they are: a layer's are upcast
    # when the layer is computed (6 GB of float32 copies would stand beside
    # the blocks' temporaries)
    params = jax.jit(lambda: adapter.program()[0](
        jax.random.PRNGKey(sample["seed"]), adapter.config(cfg)))()
    np.savez(argv[2], platform=jax.devices()[0].platform,
             **answers(params, tokens, cfg, positions, sample))


if __name__ == "__main__":
    main(sys.argv[1:])
