"""The plain reference of one chip's share of JetBrains' Mellum 2 decoder
(JetBrains/Mellum2-12B-A2.5B-Instruct, ``model_type`` ``mellum``): forward,
loss and gradients in straightforward float32 ``jax.numpy`` — no kernels, no
sort, no grouped product, no scan over stacks, the scores materialised
against an explicit ``[i, j]`` mask, matmuls at "highest" precision (a TPU
runs f32 matmuls in bf16 passes otherwise). The family's modelling code
could not be read here (there is no network); the configuration's key set is
Qwen3-MoE's and the equations are those ISSUE 43 writes out, each convention
no key gives listed under ``assumed`` in the configuration file. With
``n(.)`` an RMSNorm of ``rms_norm_eps`` and a learned weight:

every layer: ``h = h + attn(n_attn(h))``, then ``h = h + experts(n_ffn(h))``;
final RMSNorm; an untied head over the vocabulary rows held here.

Attention (``num_attention_heads`` query heads over ``num_key_value_heads``
key/value heads of ``head_dim``, which do not multiply out to the hidden
size): ``q, k, v = W_q u, W_k u, W_v u`` without bias; ``q`` and ``k``
RMS-normalised over each head's ``head_dim`` with one learned weight each;
rotary on all of ``head_dim``, halves rotated, by the layer's kind
(``layer_types``), from ``rope_parameters``:

- ``sliding_attention``: ``inv_freq[i] = theta^(-2i / head_dim)``, cos and
  sin as they are; query ``i`` sees key ``j`` where ``j <= i and i - j <
  sliding_window``;
- ``full_attention`` (YaRN): ``pair(r) = head_dim ln(original / (2 pi r)) /
  (2 ln theta)``, ``low = floor(pair(beta_fast))``, ``high =
  ceil(pair(beta_slow))``, ``ramp[i] = clip((i - low) / (high - low), 0,
  1)``, ``inv_freq[i] = (1 - ramp[i]) theta^(-2i / head_dim) + ramp[i]
  theta^(-2i / head_dim) / factor``; cos and sin are multiplied by
  ``attention_factor``; query ``i`` sees every ``j <= i``.

Scores ``q_i . k_j / sqrt(head_dim)``, softmax in f32 over the allowed ``j``
(:func:`allowed` builds the mask from the two indices), times ``v``; query
head ``h`` reads key/value head ``h // (heads / kv heads)``; ``W_o``.

Experts: ``p = softmax(z W_r)`` over all ``deployment.router_outputs``; the
``num_experts_per_tok`` largest chosen; the gates ``p`` at the chosen over
their sum (``norm_topk_prob``); the output the gated sum over the chosen
experts THAT ARE HELD HERE (``deployment.experts_held``: first and count),
each ``(silu(z W_g) * (z W_u)) W_d``. What the absent experts would add is
computed by nobody, here as in the program, and the partial sum goes on to
the next layer. No bias, no shared expert, no auxiliary term in the loss.
``described_as`` names a multi-token-prediction head; the published
configuration has no key for one and none is built.

Departures, each without effect on the values: every held expert is computed
on ALL tokens and weighted by the token's gate for it (zero where it was not
chosen or is not held), 2 experts at a time, rematerialised; attention is
taken one head and one block of queries at a time against all the keys, 8
heads' whole path at a time, rematerialised; and ``answers`` computes in
BLOCKS as ``reference_ling.py``'s does: a forward pass that keeps every half
layer's input on the host, then layer by layer backwards ``jax.vjp`` of that
half, the head in blocks of positions.

The parameter tree has the program's layout (``mellum_init``) so that both
sides can be given the same seeded weights: ``embed`` [V,D], ``lm_head``
[D,V], ``final_norm`` [D], and under ``layers`` one stack for every run of
like layers (``00_window`` [3,...], ``01_full`` [1,...] ...). It shares no
code with the program; it reads the configuration file's keys. What is no
model's own (the sampled leaves, the loss, the seeded sample) is
``reference_ling.py``'s.

Besides its answers it hands out its routing: per layer and token the
experts it chose, what each router was given (``router_in``), and ``p_kth``,
``p_next``: the k-th and (k+1)-th largest probability.

As a script (a child of the ``bare_routed`` job, which may not touch JAX
while this holds the chip):

    python3 chipbench/reference_mellum.py <config.json> <sample.json> <out.npz>
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
    BY_EXPERT, _expert_norms, _sampled, check_sample, grad_answers, loss)

EXPERTS_AT_ONCE = 2  # [experts, T, D] float32 is 302 MB an expert at 32k
HEADS_AT_ONCE = 8  # query heads whose float32 path is held at once
QUERY_BLOCK = 2048  # queries whose scores against every key are held at once
HEAD_BLOCK = 8192  # positions whose logits are held at once
FFN_LEAVES = {"ffn_norm", "router", "w_gate", "w_up", "w_down"}
KINDS = {"sliding_attention": "window", "full_attention": "full"}


def kinds(cfg):
    """``window`` or ``full`` of every kept layer."""
    return [KINDS[t] for t in cfg["layer_types"]]


def where(cfg):
    """For every layer: (the name of its run's stack, its index in it)."""
    out, run, ks = [], -1, kinds(cfg)
    for i, kind in enumerate(ks):
        if i and kind == ks[i - 1]:
            out.append((out[-1][0], out[-1][1] + 1))
        else:
            run += 1
            out.append((f"{run:02d}_{kind}", 0))
    return out


def rotary_table(cfg, kind):
    """(the rotary frequencies [head_dim / 2], the factor on cos and sin) of
    a layer of ``kind``, from ``rope_parameters``."""
    rp = cfg["rope_parameters"][{v: k for k, v in KINDS.items()}[kind]]
    hd = cfg["head_dim"]
    plain = np.asarray([rp["rope_theta"] ** (-2.0 * i / hd) for i in range(hd // 2)])
    if rp["rope_type"] == "default":
        return plain, 1.0

    def pair(turns):
        return (hd * math.log(rp["original_max_position_embeddings"] / (2 * math.pi * turns))
                / (2 * math.log(rp["rope_theta"])))

    low = max(math.floor(pair(rp["beta_fast"])), 0)  # inside the table, as transformers'
    high = min(math.ceil(pair(rp["beta_slow"])), hd - 1)
    ramp = np.clip((np.arange(hd // 2) - low) / (high - low), 0.0, 1.0)
    return (1 - ramp) * plain + ramp * plain / rp["factor"], rp["attention_factor"]


def allowed(i, j, kind, window):
    """Whether query ``i`` sees key ``j`` (arrays that broadcast)."""
    seen = j <= i
    return seen & (i - j < window) if kind == "window" else seen


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rotary(x, inv_freq, factor):
    """x [B,S,H,hd]: the halves rotated by the position's angles, cos and
    sin times ``factor``."""
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * jnp.asarray(
        inv_freq, jnp.float32)  # [S,hd/2]
    cos, sin = (factor * f(ang)[None, :, None, :] for f in (jnp.cos, jnp.sin))
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(u, w, kind, cfg, dot):
    H, K, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps, window = cfg["rms_norm_eps"], cfg["sliding_window"]
    B, S = u.shape[:2]
    inv_freq, factor = rotary_table(cfg, kind)
    block = min(QUERY_BLOCK, S)
    pad = -S % block
    keys_at = jnp.arange(S)

    @jax.checkpoint
    def block_of(q1, first, k1, v1):  # q1 [B,block,hd]; k1, v1 [B,S,hd]
        s = jnp.einsum("bqd,bkd->bqk", q1, k1) / np.sqrt(hd)
        mask = allowed((first + jnp.arange(block))[:, None], keys_at[None, :], kind, window)
        return jnp.einsum("bqk,bkd->bqd",
                          jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1), v1)

    def head(qkv):  # one query head and the key/value head it reads
        q1, k1, v1 = qkv
        blocks = jnp.moveaxis(
            jnp.pad(q1, ((0, 0), (0, pad), (0, 0))).reshape(B, -1, block, hd), 1, 0)
        firsts = jnp.arange(blocks.shape[0]) * block
        o = jax.lax.map(lambda x: block_of(x[0], x[1], k1, v1), (blocks, firsts))
        return jnp.moveaxis(o, 0, 1).reshape(B, -1, hd)[:, :S]

    @jax.checkpoint
    def some(u, wq, wk, wv):
        """Some query heads' whole path, from their columns of the
        projections to their attention output [B,S,heads*hd]; ``wk``, ``wv``
        the columns of the key/value heads they read."""
        q = _rmsnorm(dot(u, wq).reshape(B, S, -1, hd), w["q_norm"], eps)
        k = _rmsnorm(dot(u, wk).reshape(B, S, -1, hd), w["k_norm"], eps)
        v = dot(u, wv).reshape(B, S, -1, hd)
        q, k = _rotary(q, inv_freq, factor), _rotary(k, inv_freq, factor)
        reads = np.arange(q.shape[2]) // (q.shape[2] // k.shape[2])  # query head -> kv head
        a = jax.lax.map(head, (jnp.moveaxis(q, 2, 0), jnp.moveaxis(k, 2, 0)[reads],
                               jnp.moveaxis(v, 2, 0)[reads]))
        return jnp.moveaxis(a, 0, 2).reshape(B, S, -1)

    at_once = max(min(HEADS_AT_ONCE, H), H // K)  # whole groups of query heads
    outs = []
    for lo in range(0, H, at_once):
        kv = slice(lo // (H // K) * hd, (lo + at_once) // (H // K) * hd)
        outs.append(some(u, w["wq"][:, lo * hd:(lo + at_once) * hd],
                         w["wk"][:, kv], w["wv"][:, kv]))
    return dot(jnp.concatenate(outs, axis=-1), w["wo"])


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


def choose(probs, cfg):
    """probs [T,E] (softmax, float32) -> (the experts chosen [T,k], their
    gates [T,k], ``p_kth``, ``p_next`` [T])."""
    k = cfg["num_experts_per_tok"]
    top_p, top_i = jax.lax.top_k(probs, k + 1)
    gates = top_p[:, :k]
    if cfg["norm_topk_prob"]:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return top_i[:, :k].astype(jnp.int32), gates, top_p[:, k - 1], top_p[:, k]


def _routed(x, w, cfg, dot, router_dot):
    """x [T,D] -> (the held experts' part of the layer's output [T,D], its
    routing)."""
    first, held = cfg["deployment"]["experts_held"]
    probs = jax.nn.softmax(router_dot(x, w["router"]), axis=-1)  # [T, router outputs]
    idx, gates, p_k, p_n = choose(probs, cfg)
    local = idx - first  # an absent expert's column is out of range: all zeros
    weight_of = jnp.sum(jax.nn.one_hot(local, held) * gates[..., None], axis=1)
    routing = {"routing": idx, "p_kth": p_k, "p_next": p_n, "router_in": x}
    return _experts(x, weight_of, w, dot), jax.lax.stop_gradient(routing)


def mixed(kind, w, h, cfg, dot=jnp.matmul, **_):
    """A layer's first half: ``h + attn(n_attn(h))``."""
    return h + _attention(_rmsnorm(h, w["attn_norm"], cfg["rms_norm_eps"]), w, kind, cfg, dot)


def fed(kind, w, h, cfg, dot=jnp.matmul, router_dot=jnp.matmul):
    """A layer's second half: ``h + experts(n_ffn(h))`` -> (h, its routing)."""
    z = _rmsnorm(h, w["ffn_norm"], cfg["rms_norm_eps"])
    y, routing = _routed(z.reshape(-1, z.shape[-1]), w, cfg, dot, router_dot)
    return h + y.reshape(h.shape), routing


def layer(kind, w, h, cfg, **dots):
    """One layer, ``w`` its own weights (no leading axis) -> (h, its
    routing)."""
    return fed(kind, w, mixed(kind, w, h, cfg, **dots), cfg, **dots)


def _weights(params, cfg, i):
    """Layer ``i``'s weights in float32 and where they stand: (the name of
    its run's stack, its index in that stack, the weights)."""
    name, at = where(cfg)[i]
    return name, at, {k: v[at].astype(jnp.float32) for k, v in params["layers"][name].items()}


def _logits(lm_head, final_norm, h, cfg, dot):
    return dot(_rmsnorm(h, final_norm, cfg["rms_norm_eps"]), lm_head)


def forward(params, tokens, cfg, **dots):
    """tokens int [B,S] -> (logits f32 [B,S,V], the layers' routing, each
    stacked over them), all at once. ``dot`` multiplies activations by a
    weight matrix, ``router_dot`` by a router's; the tests pass ones of a
    lower precision to show that the check refuses them."""
    h, routed = params["embed"].astype(jnp.float32)[tokens], []
    for i, kind in enumerate(kinds(cfg)):
        h, r = layer(kind, _weights(params, cfg, i)[2], h, cfg, **dots)
        routed.append(r)
    logits = _logits(params["lm_head"].astype(jnp.float32),
                     params["final_norm"].astype(jnp.float32), h, cfg,
                     dots.get("dot", jnp.matmul))
    return logits, {k: jnp.stack([r[k] for r in routed]) for k in routed[0]}


def answers(params, tokens, cfg, positions, sample, **dots):
    """What the check compares: logits at ``positions`` of every sequence,
    the loss (targets = tokens, as the trainer feeds them), the global
    gradient norm, the sampled gradient leaves, and the routing: in blocks
    (see the module's text). ``params`` in any dtype; computed in f32."""
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

    # a layer is two programs forwards and two backwards: at 32k attention's
    # and the experts' temporaries do not fit the chip side by side
    def back_of(f):
        def backwards(kind, w, h, dh):
            _, back, _ = jax.vjp(lambda w, h: f(kind, w, h), w, h, has_aux=True)
            dw, dh = back(dh)
            return dw, dh, sum(jnp.sum(jnp.square(g)) for g in jax.tree_util.tree_leaves(dw))

        return jax.jit(backwards, static_argnums=0)

    halves = [lambda kind, w, h: (mixed(kind, w, h, cfg, **dots), None),
              lambda kind, w, h: fed(kind, w, h, cfg, **dots)]
    forwards = [jax.jit(f, static_argnums=0) for f in halves]
    backwards = [back_of(f) for f in halves]

    def leaves_of(w):  # each half's own: (attention's, the experts')
        return ({k: v for k, v in w.items() if k not in FFN_LEAVES},
                {k: v for k, v in w.items() if k in FFN_LEAVES})
    with jax.default_matmul_precision("highest"):
        embed = params["embed"].astype(jnp.float32)
        h, inputs, routed = embed[tokens], [], []
        for i, kind in enumerate(ks):  # the halves' inputs wait on the host
            w = _weights(params, cfg, i)[2]
            for half, own in zip(forwards, leaves_of(w)):
                inputs.append(np.asarray(h))
                h, r = half(kind, own, h)
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
    return {"logits": np.concatenate(rows, axis=1), "loss": float(val) / tokens.size,
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
        sys.exit(f"chipbench/reference_mellum.py: no TPU ({jax.devices()[0].platform})")
    tokens, positions = check_sample(cfg, sample, cfg["recipe"]["seq_len"])
    # the program's own (bf16-rounded) weights stay as they are, 2 bytes a
    # parameter; ``answers`` upcasts one layer at a time
    params = jax.jit(lambda: init_(jax.random.PRNGKey(sample["seed"]), pc))()
    np.savez(argv[2], platform=jax.devices()[0].platform,
             **answers(params, tokens, cfg, positions, sample))


if __name__ == "__main__":
    main(sys.argv[1:])
