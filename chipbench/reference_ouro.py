"""The plain reference of ByteDance's Ouro (a looped language model): forward,
every exit's logits, the expected-exit loss and its gradients in
straightforward float32 ``jax.numpy`` — no kernels, no remat of the model, no
scan: Python loops over the passes and over the layers, the scores
materialised against an explicit causal mask, matmuls at "highest" precision
(a TPU runs f32 matmuls in bf16 passes otherwise). The family's modelling
code could not be read here (there is no network); the equations are those
ISSUE 49 writes out, each convention no key gives listed under ``assumed`` in
the configuration file. With ``n(x; g) = x / sqrt(mean(x^2) + eps) * g``:

layer (four norm weights, no bias): ``h = h + n(attn(n(h; g1)); g2)``, then
``h = h + n(down(silu(gate(x)) * up(x)); g4)`` with ``x = n(h; g3)``.
Attention: ``q, k, v = W_q x, W_k x, W_v x``; rotary on all of ``head_dim``,
halves rotated, ``rope_theta``, positions from 0; scores ``q_i . k_j /
sqrt(head_dim)``, softmax in f32 over ``j <= i``; query head ``j`` reads
key/value head ``j // (heads / kv heads)``; ``W_o``.

loop: ``h_0 = E[tokens]``; for ``t = 1 .. total_ut_steps``: ``u_t`` = all
``num_hidden_layers`` layers applied to ``h_{t-1}`` (the SAME weights at
every ``t``), ``h_t = n(u_t; g_f)``: what exit ``t`` reads and what pass
``t + 1`` starts from.

exits: ``logits_t = h_t W_head``; ``lambda_t = sigmoid(h_t . w_e + b_e)``;
``p_t = lambda_t prod_{j<t} (1 - lambda_j)`` for ``t < T``, ``p_T =
prod_{j<T} (1 - lambda_j)``; ``forward`` is ``logits_T``; the loss is the
mean over tokens of ``sum_t p_t ce_t - beta H(p)``, ``H(p) = -sum_t p_t log
p_t`` (``recipe.exit_beta``).

Departures, each without effect on the values: attention is taken
``HEADS_AT_ONCE`` heads at a time, rematerialised (16 heads' float32 scores
at 4,096 are 1 GiB and their backward four times that); and ``answers``
computes in BLOCKS as ``reference_ling.py``'s does: a forward pass that keeps
every layer application's input, the exits' loss in blocks of positions, then
pass by pass and layer by layer backwards ``jax.vjp`` of that one layer, a
layer's gradient summed over the passes.

The parameter tree has the program's layout (``ouro_init``) so that both
sides can be given the same seeded weights: ``embed`` [V,D], ``lm_head``
[D,V], ``final_norm`` [D], ``exit_gate.w`` [D], ``exit_gate.b`` [], and
``layers.*`` stacked on a leading depth axis. It shares no code with the
program; it reads the configuration file's keys. What is no model's own (the
sampled leaves, the seeded sample) is ``reference.py``'s.

As a script (a child of the ``bare`` job, which may not touch JAX while this
holds the chip):

    python3 chipbench/reference_ouro.py <config.json> <sample.json> <out.npz>
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from chipbench.reference import check_sample, grad_answers  # noqa: E402,F401  (no model's own)

HEADS_AT_ONCE = 4  # query heads whose float32 scores are held at once
HEAD_BLOCK = 1024  # positions whose exits' logits are held at once


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rotary(x, theta):
    # x [B,S,H,hd]; rotate_half: pairs are (i, i + hd/2)
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv  # [S,hd/2]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


@jax.checkpoint
def _heads(q, k, v):
    """Causal attention of a few heads: q, k, v [B,S,h,hd] -> [B,S,h,hd]."""
    S = q.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def layer(w, h, cfg, dot=jnp.matmul):
    """One sandwich-norm layer: ``w`` its leaves, h [B,S,D]."""
    hq, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    B, S = h.shape[:2]
    x = _rmsnorm(h, w["attn_norm"], eps)
    q = _rotary(dot(x, w["wq"]).reshape(B, S, hq, hd), theta)
    k = _rotary(dot(x, w["wk"]).reshape(B, S, hkv, hd), theta)
    v = dot(x, w["wv"]).reshape(B, S, hkv, hd)
    k = jnp.repeat(k, hq // hkv, axis=2)  # query head j reads kv head j // g
    v = jnp.repeat(v, hq // hkv, axis=2)
    a = jnp.concatenate([_heads(q[:, :, i:i + HEADS_AT_ONCE], k[:, :, i:i + HEADS_AT_ONCE],
                                v[:, :, i:i + HEADS_AT_ONCE])
                         for i in range(0, hq, HEADS_AT_ONCE)], axis=2)
    h = h + _rmsnorm(dot(a.reshape(B, S, hq * hd), w["wo"]), w["attn_post_norm"], eps)
    x = _rmsnorm(h, w["ffn_norm"], eps)
    m = dot(jax.nn.silu(dot(x, w["w_gate"])) * dot(x, w["w_up"]), w["w_down"])
    return h + _rmsnorm(m, w["ffn_post_norm"], eps)


def _weights(params, i):
    return {k: v[i] for k, v in params["layers"].items()}


def exits(params, tokens, cfg, dot=jnp.matmul):
    """tokens int [B,S] -> every exit's hidden state, a list of T [B,S,D]."""
    h, out = params["embed"][tokens], []
    for _ in range(cfg["total_ut_steps"]):
        for i in range(cfg["num_hidden_layers"]):
            h = layer(_weights(params, i), h, cfg, dot)
        h = _rmsnorm(h, params["final_norm"], cfg["rms_norm_eps"])
        out.append(h)
    return out


def exit_logits(params, tokens, cfg, dot=jnp.matmul):
    """Every exit's logits, f32 [T,B,S,V]."""
    return jnp.stack([dot(h, params["lm_head"]) for h in exits(params, tokens, cfg, dot)])


def forward(params, tokens, cfg, dot=jnp.matmul):
    """The last exit's logits, f32 [B,S,V]: no early exit."""
    return dot(exits(params, tokens, cfg, dot)[-1], params["lm_head"])


def exit_probs(lam):
    """lambda [T,...] -> p [T,...]: exit ``t`` takes ``lambda_t`` of what the
    exits before it left, the last one all that is left."""
    T, left, out = lam.shape[0], jnp.ones_like(lam[0]), []
    for t in range(T):
        out.append(left * lam[t] if t < T - 1 else left)
        left = left * (1.0 - lam[t])
    return jnp.stack(out)


def token_losses(hs, lm_head, gate, targets, beta, dot=jnp.matmul):
    """Of the exits' states hs [T,B,S,D] -> (the loss token by token [B,S],
    the exit distribution p [T,B,S], the cross-entropies [T,B,S])."""
    logp = jax.nn.log_softmax(dot(hs, lm_head), axis=-1)
    ce = -jnp.take_along_axis(
        logp, jnp.broadcast_to(targets, hs.shape[:-1])[..., None], axis=-1)[..., 0]
    p = exit_probs(jax.nn.sigmoid(jnp.einsum("tbsd,d->tbs", hs, gate["w"]) + gate["b"]))
    entropy = -jnp.sum(jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1.0)), 0.0), axis=0)
    return jnp.sum(p * ce, axis=0) - beta * entropy, p, ce


def loss(params, tokens, targets, cfg, dot=jnp.matmul):
    """The expected-exit loss, whole (the tests' sizes)."""
    hs = jnp.stack(exits(params, tokens, cfg, dot))
    return jnp.mean(token_losses(hs, params["lm_head"], params["exit_gate"], targets,
                                 cfg["recipe"]["exit_beta"], dot)[0])


def answers(params, tokens, cfg, positions, sample, dot=jnp.matmul):
    """What the check compares: the last exit's logits at ``positions`` of
    every sequence, the loss (targets = tokens, as the trainer feeds them),
    the global gradient norm and the sampled gradient leaves; in blocks (the
    module's text). ``params`` in any dtype; computed in f32."""
    T, L, beta = cfg["total_ut_steps"], cfg["num_hidden_layers"], cfg["recipe"]["exit_beta"]
    eps, n_tokens = cfg["rms_norm_eps"], tokens.size
    p32 = jax.tree_util.tree_map(
        lambda x: x if x.dtype == jnp.float32 else x.astype(jnp.float32), params)
    add = lambda a, b: jax.tree_util.tree_map(jnp.add, a, b)  # noqa: E731

    step = jax.jit(lambda w, h: layer(w, h, cfg, dot))
    between = jax.jit(lambda g, u: _rmsnorm(u, g, eps))

    @jax.jit
    def layer_back(w, h, dh):
        _, pull = jax.vjp(lambda w, h: layer(w, h, cfg, dot), w, h)
        return pull(dh)

    @jax.jit
    def between_back(g, u, dh):
        _, pull = jax.vjp(between, g, u)
        return pull(dh)

    @jax.jit
    def head_block(lm_head, gate, hs, targets):  # a block of positions: sums
        def f(lm_head, gate, hs):
            return jnp.sum(token_losses(hs, lm_head, gate, targets, beta, dot)[0]) / n_tokens
        val, pull = jax.vjp(f, lm_head, gate, hs)
        return (val, *pull(jnp.ones((), jnp.float32)))

    with jax.default_matmul_precision("highest"):
        # forward: every layer application's input, every pass's last state
        h, kept, us, hs = p32["embed"][tokens], [], [], []
        for _ in range(T):
            for i in range(L):
                kept.append(h)
                h = step(_weights(p32, i), h)
            us.append(h)
            h = between(p32["final_norm"], h)
            hs.append(h)
        hs = jnp.stack(hs)
        logits = dot(hs[-1][:, positions], p32["lm_head"])
        # the exits: loss and its gradient block of positions by block
        val, d_head, d_gate, d_hs = 0.0, None, None, []
        for s in range(0, tokens.shape[1], HEAD_BLOCK):
            v, dl, dg, dh = head_block(p32["lm_head"], p32["exit_gate"],
                                       hs[:, :, s:s + HEAD_BLOCK], tokens[:, s:s + HEAD_BLOCK])
            val, d_hs = val + v, d_hs + [dh]
            d_head, d_gate = (dl, dg) if d_head is None else (d_head + dl, add(d_gate, dg))
        d_hs = jnp.concatenate(d_hs, axis=2)
        # backwards: pass by pass, a layer's gradient the sum over the passes
        d_layers, d_final, dh = [None] * L, None, None
        for t in reversed(range(T)):
            dg, dh = between_back(p32["final_norm"], us[t],
                                  d_hs[t] if dh is None else d_hs[t] + dh)
            d_final = dg if d_final is None else d_final + dg
            for i in reversed(range(L)):
                dw, dh = layer_back(_weights(p32, i), kept[t * L + i], dh)
                d_layers[i] = dw if d_layers[i] is None else add(d_layers[i], dw)
        del kept, us, hs
        # stacked leaf by leaf, a layer's piece let go as it is read: two
        # whole trees of float32 layer gradients do not stand side by side
        grads = {"embed": jnp.zeros_like(p32["embed"]).at[tokens].add(dh),
                 "layers": {k: jnp.stack([d.pop(k) for d in d_layers])
                            for k in list(d_layers[0])},
                 "final_norm": d_final, "lm_head": d_head, "exit_gate": d_gate}
        sampled = jax.jit(lambda g: grad_answers(g, sample))(grads)
    return {"logits": np.asarray(logits), "loss": float(val),
            **{k: np.asarray(v) for k, v in sampled.items()}}


def main(argv):
    from chipbench import manifest

    if jax.devices()[0].platform != "tpu":  # before any work: no CPU answers
        sys.exit(f"chipbench/reference_ouro.py: no TPU ({jax.devices()[0].platform})")
    with open(argv[0]) as f, open(argv[1]) as g:
        cfg, sample = json.load(f), json.load(g)
    adapter = manifest.adapter_for(argv[0], cfg)  # the program's init, for equal weights
    tokens, positions = check_sample(cfg, sample, cfg["recipe"]["seq_len"])
    # the program's bf16-rounded weights, upcast in the same call: the bf16
    # copy does not stay beside 4 bytes a parameter of weights and gradients
    params = jax.jit(lambda: jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32),
        adapter.program()[0](jax.random.PRNGKey(sample["seed"]), adapter.config(cfg))))()
    np.savez(argv[2], platform=jax.devices()[0].platform,
             **answers(params, tokens, cfg, positions, sample))


if __name__ == "__main__":
    main(sys.argv[1:])
