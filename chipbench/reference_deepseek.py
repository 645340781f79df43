"""The plain reference of one chip's share of DeepSeek-V2
(deepseek-ai/DeepSeek-V2, ``model_type`` ``deepseek_v2``; arXiv:2405.04434):
forward, loss and gradients in straightforward float32 ``jax.numpy`` — no
kernels, no sort, no grouped product, no scan over stacks, the scores
materialised against an explicit causal mask, matmuls at "highest" precision
(a TPU runs f32 matmuls in bf16 passes otherwise). The equations are those of
``modeling_deepseek.py`` in the source repository as published (there is no
network here; they are written from memory of that file and the paper, and
the configuration file lists every convention no key gives under
``assumed``). With ``n(.)`` an RMSNorm of ``rms_norm_eps`` and a learned
weight:

every layer: ``h = h + mla(n_in(h))``, then ``h = h + ffn(n_ffn(h))``; layer
``j`` of the cut has a dense SwiGLU of ``intermediate_size`` where ``j <
first_k_dense_replace`` and routed experts otherwise (``moe_layer_freq`` 1);
final RMSNorm; an untied head over the vocabulary rows held here; no bias
anywhere.

MLA, over the ``num_attention_heads`` heads HELD HERE (the key counts them;
``published`` has the layer's 128): ``c_q = n(W_dq u)`` (``q_lora_rank``),
``q = W_uq c_q`` a head ``qk_nope_head_dim + qk_rope_head_dim``; ``[c_kv,
k_r] = W_dkv u`` (``kv_lora_rank`` + rotary), ``c_kv <- n(c_kv)``, ``[k_n,
v] = W_ukv c_kv`` a head ``qk_nope_head_dim + v_head_dim``; ``k_r`` is one
rotary key shared by all heads. Rotary on ``q``'s last ``qk_rope_head_dim``
and on ``k_r``, the stored values paired (0, 1), (2, 3).., base
``rope_theta``, the inverse frequencies YaRN's (``rope_scaling``): ``pair(r)
= dim ln(original / (2 pi r)) / (2 ln theta)`` over ``dim`` = the rotary
width, ``low = floor(pair(beta_fast))``, ``high = ceil(pair(beta_slow))``
(inside the table), ``ramp[i] = clip((i - low) / (high - low), 0, 1)``,
``inv_freq[i] = (1 - ramp[i]) theta^(-2i / dim) + ramp[i] theta^(-2i / dim)
/ factor``; cos and sin times ``mscale(factor, mscale) / mscale(factor,
mscale_all_dim)``, ``mscale(s, m) = 0.1 m ln s + 1``. Scores ``(q_n . k_n +
q_r . k_r) (dn + dr)^-1/2 mscale(factor, mscale_all_dim)^2``, causal,
softmax in float32, times ``v``; ``W_o`` from the held heads' values to the
hidden size: THE HELD HEADS' PART of the sum over heads. What the other
heads would add is computed by nobody, here as in the program, and the
partial sum goes on to the next layer. No gate, no cache.

Experts: ``p = softmax(z W_r)`` over all ``deployment.router_outputs``; each
of ``n_group`` contiguous groups scored by its LARGEST ``p``
(``group_limited_greedy``), the best ``topk_group`` kept; the
``num_experts_per_tok`` largest inside them chosen; the gates ``p`` at the
chosen, not renormalised (``norm_topk_prob`` false), times
``routed_scaling_factor``; the output the shared experts (one SwiGLU of
``n_shared_experts x moe_intermediate_size``, ungated) plus the gated sum
over the chosen experts THAT ARE HELD HERE (``deployment.experts_held``).

Loss: the mean cross-entropy over the vocabulary held plus, for every routed
layer, ``aux_loss_alpha`` (``assumed``) times the mean over sequences of
``sum_e f_e P_e``: ``f_e`` = (the sequence's tokens that chose e among
their k) x E / (k S), a constant; ``P_e`` = the sequence's mean of ``p_e``
(``seq_aux``), over all E router outputs.

Departures, each without effect on the values: the source fills the dropped
groups' scores with 0.0 where this puts -inf (the same choice: softmax
scores are positive); every held expert is computed on ALL tokens and
weighted by the token's gate for it (zero where it was not chosen or is not
held), 2 experts at a time, rematerialised (``reference_ling._experts``);
attention is taken one head and one block of queries at a time against all
the keys, rematerialised; a dense SwiGLU runs over blocks of positions; and ``answers`` computes in BLOCKS as
``reference_mellum.py``'s does: a forward pass that keeps every half layer's
input on the host, then layer by layer backwards ``jax.vjp`` of that half
(the balance term's cotangent ``aux_loss_alpha`` beside the hidden
state's), the head in blocks of positions.

The parameter tree has the program's layout (``deepseek_init``) so that both
sides can be given the same seeded weights: ``embed`` [V,D], ``lm_head``
[D,V], ``final_norm`` [D], and under ``layers`` one stack for every run of
like layers (``00_dense`` [1,...], ``01_moe`` [1,...] ...). It shares no code
with the program; it reads the configuration file's keys. What is no model's
own (the sampled leaves, the seeded sample, an RMSNorm, a SwiGLU, the held
experts on every token) is ``reference_ling.py``'s.

Besides its answers it hands out its routing: per expert layer and token the
experts it chose, what each router was given (``router_in``), and ``p_kth``,
``p_next``: the k-th and (k+1)-th largest ``p`` inside the kept groups,
``p_next`` raised to ``p_kth`` times the best dropped group's score over the
last kept one's where that is more (the nearer of the two ties a token's
choice rests on, as ``reference_ling.py``'s), and ``balance``: each expert
layer's term of the loss before ``aux_loss_alpha``.

As a script (a child of the ``bare_routed`` job, which may not touch JAX
while this holds the chip):

    python3 chipbench/reference_deepseek.py <config.json> <sample.json> <out.npz>
"""

import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from chipbench.reference_ling import (  # noqa: E402,F401  (no model's own)
    BY_EXPERT, _expert_norms, _experts, _logits, _rmsnorm, _sampled, _swiglu, check_sample,
    grad_answers)

QUERY_BLOCK = 2048  # queries whose scores against every key are held at once
FFN_BLOCK = 4096  # positions of a dense SwiGLU held at once
HEAD_BLOCK = 8192  # positions whose logits are held at once
FFN_LEAVES = {"ffn_norm", "router", "w_gate", "w_up", "w_down", "shared_gate", "shared_up",
              "shared_down"}


def kinds(cfg):
    """``dense`` or ``moe`` of every kept layer."""
    return ["dense" if j < cfg["first_k_dense_replace"] else "moe"
            for j in range(cfg["num_hidden_layers"])]


def where(cfg):
    """For every layer: (the name of its run's stack, its index in it); the
    dense layers stand in one stack, an expert layer in its own."""
    out, run, ks = [], -1, kinds(cfg)
    for i, kind in enumerate(ks):
        if i and kind == ks[i - 1] == "dense":
            out.append((out[-1][0], out[-1][1] + 1))
        else:
            run += 1
            out.append((f"{run:02d}_{kind}", 0))
    return out


def mscale(scale, m):
    return 0.1 * m * math.log(scale) + 1.0 if scale > 1 else 1.0


def yarn(cfg):
    """(the rotary frequencies [qk_rope_head_dim / 2], the factor on cos and
    sin, the factor on the softmax scale), from ``rope_scaling``."""
    rs, dim, theta = cfg["rope_scaling"], cfg["qk_rope_head_dim"], cfg["rope_theta"]
    plain = np.asarray([theta ** (-2.0 * i / dim) for i in range(dim // 2)])

    def pair(turns):
        return (dim * math.log(rs["original_max_position_embeddings"] / (2 * math.pi * turns))
                / (2 * math.log(theta)))

    low = max(math.floor(pair(rs["beta_fast"])), 0)
    high = min(math.ceil(pair(rs["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 0.001), 0.0, 1.0)
    all_dim = mscale(rs["factor"], rs["mscale_all_dim"])
    return ((1 - ramp) * plain + ramp * plain / rs["factor"],
            mscale(rs["factor"], rs["mscale"]) / all_dim, all_dim ** 2)


def _rotary(x, inv_freq, factor):
    """x [B,S,H,dr], its values paired (0, 1), (2, 3).. as stored: each pair
    turned by its position's angle, cos and sin times ``factor``; the result
    lists the firsts of the pairs, then the seconds (both sides of a score
    are listed alike, so the order is without effect)."""
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * jnp.asarray(
        inv_freq, jnp.float32)  # [S,dr/2]
    cos, sin = (factor * f(ang)[None, :, None, :] for f in (jnp.cos, jnp.sin))
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def latents(u, w, cfg, dot):
    """What every chip of the layer computes alike: the queries' normalised
    latent [B,S,q_lora_rank], the keys' and values' [B,S,kv_lora_rank] and
    the rotary key [B,S,1,dr], turned."""
    r, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    inv_freq, factor, _ = yarn(cfg)
    c_q = _rmsnorm(dot(u, w["w_dq"]), w["q_norm"], eps)
    ckr = dot(u, w["w_kva"])
    return (c_q, _rmsnorm(ckr[..., :r], w["kv_norm"], eps),
            _rotary(ckr[..., None, r:], inv_freq, factor))


def heads_part(c_q, c, k_r, w_uq, w_kvb, wo, cfg, dot):
    """Some heads' part of the mixer's output [B,S,D], from their columns of
    ``w_uq`` and ``w_kvb`` to their rows of ``wo``."""
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    B, S = c_q.shape[:2]
    inv_freq, factor, softmax_factor = yarn(cfg)
    scale = softmax_factor / np.sqrt(dn + dr)
    block = min(QUERY_BLOCK, S)
    pad = -S % block
    at = jnp.arange(S)

    @jax.checkpoint
    def block_of(q1, first, k1, v1):  # q1 [B,block,d]; k1 [B,S,d]; v1 [B,S,dv]
        s = jnp.einsum("bqd,bkd->bqk", q1, k1) * scale
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

    q = dot(c_q, w_uq).reshape(B, S, -1, dn + dr)
    kv = dot(c, w_kvb).reshape(B, S, -1, dn + dv)
    q = jnp.concatenate([q[..., :dn], _rotary(q[..., dn:], inv_freq, factor)], axis=-1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_r, kv.shape[:3] + (dr,))], axis=-1)
    a = jax.lax.map(head, tuple(jnp.moveaxis(m, 2, 0) for m in (q, k, kv[..., dn:])))
    return dot(jnp.moveaxis(a, 0, 2).reshape(B, S, -1), wo)


def _mla(u, w, cfg, dot):
    return heads_part(*latents(u, w, cfg, dot), w["w_uq"], w["w_kvb"], w["wo"], cfg, dot)


def _swiglu_blocks(x, wg, wu, wd, dot):
    """``_swiglu`` of x [T,D] over blocks of ``FFN_BLOCK`` positions, each
    rematerialised."""
    T = x.shape[0]
    if T <= FFN_BLOCK or T % FFN_BLOCK:
        return _swiglu(x, wg, wu, wd, dot)
    one = jax.checkpoint(lambda rows: _swiglu(rows, wg, wu, wd, dot))
    return jax.lax.map(one, x.reshape(-1, FFN_BLOCK, x.shape[-1])).reshape(x.shape)


def choose(probs, cfg):
    """probs [T,E] (softmax, float32) -> (the experts chosen [T,k], their
    gates [T,k], ``p_kth``, ``p_next`` [T]: see the module's text)."""
    T, E = probs.shape
    k, groups, kept_n = cfg["num_experts_per_tok"], cfg["n_group"], cfg["topk_group"]
    if cfg["topk_method"] != "group_limited_greedy":
        raise ValueError(f"topk_method {cfg['topk_method']!r}")
    group_score = jnp.max(probs.reshape(T, groups, E // groups), axis=-1)  # its best
    order = jnp.argsort(-group_score, axis=-1)
    ranked = jnp.take_along_axis(group_score, order, axis=-1)
    kept = jnp.zeros((T, groups), bool).at[
        jnp.arange(T)[:, None], order[:, :kept_n]].set(True)
    inside = jnp.where(jnp.repeat(kept, E // groups, axis=1), probs, -jnp.inf)
    top_p, top_i = jax.lax.top_k(inside, k + 1)
    idx = top_i[:, :k]
    gates = top_p[:, :k]
    if cfg["norm_topk_prob"]:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    else:  # the source scales where it does not renormalise
        gates = gates * cfg["routed_scaling_factor"]
    p_k, p_n = top_p[:, k - 1], top_p[:, k]
    if kept_n < groups:  # the groups' tie where it is the nearer
        p_n = jnp.maximum(p_n, p_k * (ranked[:, kept_n] / ranked[:, kept_n - 1]))
    return idx.astype(jnp.int32), gates, p_k, p_n


def balance(probs, idx, sequences):
    """probs [T,E], idx [T,k], T = sequences x S -> the layer's term of the
    loss before ``aux_loss_alpha``: the mean over sequences of ``sum_e f_e
    P_e``."""
    (T, E), k = probs.shape, idx.shape[1]
    S = T // sequences
    chose = jnp.sum(jax.nn.one_hot(idx.reshape(sequences, S * k), E), axis=1)  # [B,E]
    f = jax.lax.stop_gradient(chose * E / (k * S))
    return jnp.mean(jnp.sum(f * jnp.mean(probs.reshape(sequences, S, E), axis=1), axis=-1))


def gate_of(idx, gates, first, count):
    """[T,count]: a token's gate for each of experts first .. first + count
    - 1, zero where it did not choose it."""
    return jnp.sum(jax.nn.one_hot(idx - first, count) * gates[..., None], axis=1)


def _routed(x, w, cfg, sequences, dot, router_dot):
    """x [T,D] -> (the shared experts plus the held experts' part of the
    routed sum [T,D], the layer's balance term, its routing)."""
    first, held = cfg["deployment"]["experts_held"]
    probs = jax.nn.softmax(router_dot(x, w["router"]), axis=-1)  # [T, router outputs]
    idx, gates, p_k, p_n = choose(probs, cfg)
    term = balance(probs, idx, sequences)
    routing = {"routing": idx, "p_kth": p_k, "p_next": p_n, "router_in": x, "balance": term}
    y = (_experts(x, gate_of(idx, gates, first, held), w, dot)
         + _swiglu(x, w["shared_gate"], w["shared_up"], w["shared_down"], dot))
    return y, term, jax.lax.stop_gradient(routing)


def mixed(kind, w, h, cfg, dot=jnp.matmul, **_):
    """A layer's first half: ``h + mla(n_in(h))``."""
    return h + _mla(_rmsnorm(h, w["norm"], cfg["rms_norm_eps"]), w, cfg, dot)


def fed(kind, w, h, cfg, dot=jnp.matmul, router_dot=jnp.matmul):
    """A layer's second half: ``h + ffn(n_ffn(h))`` -> (h, its balance term
    (0 for a dense layer), its routing or None)."""
    z = _rmsnorm(h, w["ffn_norm"], cfg["rms_norm_eps"])
    flat = z.reshape(-1, z.shape[-1])
    if kind == "dense":
        y = _swiglu_blocks(flat, w["w_gate"], w["w_up"], w["w_down"], dot)
        return h + y.reshape(h.shape), jnp.zeros((), jnp.float32), None
    y, term, routing = _routed(flat, w, cfg, h.shape[0], dot, router_dot)
    return h + y.reshape(h.shape), term, routing


def layer(kind, w, h, cfg, **dots):
    """One layer, ``w`` its own weights (no leading axis) -> (h, its balance
    term, its routing or None)."""
    return fed(kind, w, mixed(kind, w, h, cfg, **dots), cfg, **dots)


def _weights(params, cfg, i):
    """Layer ``i``'s weights in float32 and where they stand: (the name of
    its run's stack, its index in that stack, the weights)."""
    name, at = where(cfg)[i]
    return name, at, {k: v[at].astype(jnp.float32) for k, v in params["layers"][name].items()}


def forward(params, tokens, cfg, **dots):
    """tokens int [B,S] -> (logits f32 [B,S,V], the expert layers' routing,
    each stacked over them; ``balance`` [L] among it), all at once. ``dot``
    multiplies activations by a weight matrix, ``router_dot`` by a router's;
    the tests pass ones of a lower precision to show that the check refuses
    them."""
    h, routed = params["embed"].astype(jnp.float32)[tokens], []
    for i, kind in enumerate(kinds(cfg)):
        h, _, r = layer(kind, _weights(params, cfg, i)[2], h, cfg, **dots)
        if r is not None:
            routed.append(r)
    logits = _logits(params["lm_head"].astype(jnp.float32),
                     params["final_norm"].astype(jnp.float32), h, cfg,
                     dots.get("dot", jnp.matmul))
    return logits, {k: jnp.stack([r[k] for r in routed]) for k in routed[0]}


def loss(logits, targets, balance_terms=None, alpha=0.0):
    """Mean cross-entropy of logits[b, s] against targets[b, s], over the
    vocabulary rows held here, plus ``alpha`` times the expert layers'
    balance terms (``forward``'s ``balance``)."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))
    return ce if balance_terms is None else ce + alpha * jnp.sum(balance_terms)


def loss_of(params, tokens, cfg, **dots):
    """The whole loss at once (the tests' small sizes), differentiable in
    ``params``: the layers' balance terms with their gradient."""
    h, terms = params["embed"].astype(jnp.float32)[tokens], 0.0
    for i, kind in enumerate(kinds(cfg)):
        h, term, _ = layer(kind, _weights(params, cfg, i)[2], h, cfg, **dots)
        terms = terms + term
    logits = _logits(params["lm_head"].astype(jnp.float32),
                     params["final_norm"].astype(jnp.float32), h, cfg,
                     dots.get("dot", jnp.matmul))
    return loss(logits, tokens) + cfg["aux_loss_alpha"] * terms


def answers(params, tokens, cfg, positions, sample, **dots):
    """What the check compares: logits at ``positions`` of every sequence,
    the loss (targets = tokens, as the trainer feeds them; the balance terms
    in it), the global gradient norm, the sampled gradient leaves, and the
    routing: in blocks (see the module's text). ``params`` in any dtype;
    computed in f32."""
    ks = kinds(cfg)
    dot = dots.get("dot", jnp.matmul)
    alpha = jnp.asarray(cfg["aux_loss_alpha"], jnp.float32)
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

    # a layer is two programs forwards and two backwards: attention's and the
    # experts' temporaries need not fit the chip side by side
    def back_of(f):
        def backwards(kind, w, h, dh):
            _, back, _ = jax.vjp(lambda w, h: f(kind, w, h), w, h, has_aux=True)
            dw, dh = back((dh, alpha))  # the loss adds alpha x the half's term
            return dw, dh, sum(jnp.sum(jnp.square(g)) for g in jax.tree_util.tree_leaves(dw))

        return jax.jit(backwards, static_argnums=0)

    def second(kind, w, h):
        out, term, routing = fed(kind, w, h, cfg, **dots)
        return (out, term), routing

    halves = [lambda kind, w, h: ((mixed(kind, w, h, cfg, **dots),
                                   jnp.zeros((), jnp.float32)), None), second]
    forwards = [jax.jit(f, static_argnums=0) for f in halves]
    backwards = [back_of(f) for f in halves]

    def leaves_of(w):  # each half's own: (the mixer's, the feed-forward's)
        return ({k: v for k, v in w.items() if k not in FFN_LEAVES},
                {k: v for k, v in w.items() if k in FFN_LEAVES})
    with jax.default_matmul_precision("highest"):
        embed = params["embed"].astype(jnp.float32)
        h, inputs, routed, terms = embed[tokens], [], [], 0.0
        for i, kind in enumerate(ks):  # the halves' inputs wait on the host
            w = _weights(params, cfg, i)[2]
            for half, own in zip(forwards, leaves_of(w)):
                inputs.append(np.asarray(h))
                (h, term), r = half(kind, own, h)
                terms = terms + float(term)
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
            name, at, w = _weights(params, cfg, i)
            for half, own in zip(reversed(backwards), reversed(leaves_of(w))):
                dw, dh, sq = half(ks[i], own, jnp.asarray(inputs.pop()), dh)
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
    return {"logits": np.concatenate(rows, axis=1),
            "loss": float(val) / tokens.size + float(alpha) * terms,
            "grad_norm": np.asarray(jnp.sqrt(squares)),
            **{"grad." + p: np.concatenate([np.asarray(got[at]) for at in sorted(got)])
               for p, got in wanted.items()},
            **{k: np.stack([r[k] for r in routed]) for k in routed[0]}}


def main(argv):
    from chipbench import manifest

    with open(argv[0]) as f, open(argv[1]) as g:
        cfg, sample = json.load(f), json.load(g)
    # the program's init, for equal weights; a program that cannot express
    # the configuration ends here, before this process asks for the chip
    adapter = manifest.adapter_for(argv[0], cfg)
    init_, pc = adapter.program()[0], adapter.config(cfg)
    if jax.devices()[0].platform != "tpu":  # before any work: no CPU answers
        sys.exit(f"chipbench/reference_deepseek.py: no TPU ({jax.devices()[0].platform})")
    tokens, positions = check_sample(cfg, sample, cfg["recipe"]["seq_len"])
    # the program's own (bf16-rounded) weights stay as they are, 2 bytes a
    # parameter; ``answers`` upcasts one layer at a time
    params = jax.jit(lambda: init_(jax.random.PRNGKey(sample["seed"]), pc))()
    np.savez(argv[2], platform=jax.devices()[0].platform,
             **answers(params, tokens, cfg, positions, sample))


if __name__ == "__main__":
    main(sys.argv[1:])
