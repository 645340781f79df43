"""The plain reference of one chip's share of DeepSeek-V3.2-Exp
(deepseek-ai/DeepSeek-V3.2-Exp, ``model_type`` ``deepseek_v32``) in the DENSE
WARM-UP STAGE of its continued training ("DeepSeek-V3.2-Exp: Boosting
Long-Context Efficiency with DeepSeek Sparse Attention": dense attention
kept, every parameter frozen but the lightning indexers', each indexer
trained by a KL divergence against its layer's own attention): forward, the
stage's loss and its gradients in straightforward float32 ``jax.numpy`` — no
kernels, no sort, no grouped product, no scan over stacks, every score matrix
materialised (a block of queries at a time) against an explicit causal mask,
matmuls at "highest" precision. The equations are those of the source
repository's ``inference/model.py`` (``MLA``, ``Indexer``, ``Gate``, ``MoE``)
and of the report, written from memory of both (there is no network here);
the configuration file lists every convention no key gives under ``assumed``.
With ``n(.)`` an RMSNorm of ``rms_norm_eps`` and a learned weight:

every layer: ``h = h + mla(n_in(h))``, then ``h = h + ffn(n_ffn(h))``; layer
``j`` of the cut has a dense SwiGLU of ``intermediate_size`` where ``j <
first_k_dense_replace`` and routed experts otherwise; NO final norm and NO
head: the stage predicts nothing, and what the check compares in the place
of logits is the last layer's output.

MLA over all ``num_attention_heads`` heads, exactly as
``reference_deepseek.py`` writes it (``c_q = n(W_dq u)``, ``q = W_uq c_q``,
``[c_kv, k_r] = W_dkv u``, ``c_kv <- n(c_kv)``, ``[k_n, v] = W_ukv c_kv``,
rotary on ``q``'s last ``qk_rope_head_dim`` and on the one shared ``k_r``,
stored pairs (0, 1), (2, 3).., YaRN's frequencies; scores ``(q_n . k_n + q_r
. k_r) (dn + dr)^-1/2 mscale(factor, mscale_all_dim)^2``, causal softmax in
float32, times ``v``, ``W_o``): ``yarn`` and ``latents`` are that file's.

The lightning indexer of a layer (``index_n_heads`` = HI heads of
``index_head_dim`` = dI; ``u`` the layer's normalised input)::

    qI = W_Iq c_q                     [T, HI, dI]   rotary on [..., :dr], as two HALVES
    kI = LayerNorm(W_Ik u)            [T, dI]       weight AND bias, eps 1e-6; rotary on [:dr]
    w  = (W_Iw u) HI^-1/2 dI^-1/2     [T, HI]
    I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])        s <= t

(the rotary turn of the indexer is over the same YaRN frequencies and
positions, the FIRST ``dr`` of the ``dI`` values taken as halves ``[x1,
x2]`` -> ``[x1 cos - x2 sin, x2 cos + x1 sin]``: the source's
``Indexer.forward``).

The stage's loss: ``P_h`` head ``h``'s attention probabilities, ``p[t, :] =
sum_h P_h[t, :] / H`` (a constant: every leaf it reads is frozen), ``L_layer
= mean_t KL(p[t, :t+1] || softmax(I[t, :t+1]))``, the loss the sum of
``L_layer`` over the layers held; gradients by ``jax.grad`` into the five
indexer leaves of every layer.

Experts: ``s = sigmoid(z W_r)`` over all ``deployment.router_outputs``; the
selection on ``s + expert_bias`` (``noaux_tc``): each of ``n_group``
contiguous groups scored by the SUM OF ITS BEST TWO, the best ``topk_group``
kept, the ``num_experts_per_tok`` largest inside them chosen; the gates ``s``
(without the bias) at the chosen over their sum + 1e-20 (``norm_topk_prob``),
times ``routed_scaling_factor``; the output the shared expert (one SwiGLU of
``n_shared_experts x moe_intermediate_size``) plus the gated sum over the
chosen experts THAT ARE HELD HERE (``deployment.experts_held``); what the
other experts would add is computed by nobody, here as in the program.

**The model's published forward, which the program does not build**:
``forward(..., sparse=True)`` lets every query attend to the ``index_topk``
keys with the largest ``I[t, :t+1]`` alone (``select``), the whole ``[T,
T]`` in memory: small sizes only. With ``index_topk >= T`` it is the dense
forward, which a test shows.

Departures, each without effect on the values: every held expert is computed
on ALL tokens and weighted by the token's gate for it
(``reference_ling._experts``); the attention, the target and the indexer's
scores are taken a block of ``QUERY_BLOCK`` queries at a time against all the
keys, every head at once; a dense SwiGLU runs over blocks of positions;
``answers`` keeps the weights on the host and upcasts half a layer at a time.

The parameter tree has the program's layout (``deepseek_init`` under
``dsa_stage="warmup"``): ``embed`` [V,D], ``expert_bias`` [expert layers, E],
under ``layers`` one stack for every run of like layers (``00_dense``
[1,...], ``01_moe`` [1,...] ...) and under ``indexer`` the same runs with
``w_iq``, ``w_ik``, ``k_norm``, ``k_bias``, ``w_iw``. It shares no code with
the program; it reads the configuration file's keys. What is no model's own
(the sampled leaves, the seeded sample, an RMSNorm, a SwiGLU, the held
experts on every token) is ``reference_ling.py``'s, YaRN and the latents
``reference_deepseek.py``'s.

Besides its answers it hands out its routing as ``reference_deepseek.py``
does (``routing``, ``router_in``, ``p_kth``, ``p_next``) and ``layer_losses``,
each layer's ``L_layer``.

As a script (a child of the ``bare_frozen`` job, which may not touch JAX
while this holds the chip):

    python3 chipbench/reference_deepseek_v32.py <config.json> <sample.json> <out.npz>
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from chipbench.reference_deepseek import (  # noqa: E402,F401  (YaRN, the latents, the runs)
    FFN_LEAVES, _swiglu_blocks, gate_of, kinds, latents, where, yarn)
from chipbench.reference_ling import (  # noqa: E402,F401  (no model's own)
    _experts, _rmsnorm, _sampled, _swiglu, check_sample, expert_bias, grad_answers)

QUERY_BLOCK = 128  # queries whose scores against every key, every head, are held at once
GATE_EPS = 1e-20


def _layernorm(x, weight, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * weight + bias


def _rotary_halves(x, inv_freq, first=0):
    """x [B,S,h,d] at positions ``first`` ..: its first ``2 len(inv_freq)``
    values turned as two halves, the rest as they are."""
    half = len(inv_freq)
    ang = (first + jnp.arange(x.shape[1], dtype=jnp.float32))[:, None] * jnp.asarray(
        inv_freq, jnp.float32)
    cos, sin = (f(ang)[None, :, None, :] for f in (jnp.cos, jnp.sin))
    a, b = x[..., :half], x[..., half:2 * half]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, x[..., 2 * half:]], axis=-1)


def index_keys(ix, u, cfg, dot):
    """The indexer's one key a position [B,S,dI], turned."""
    kI = _layernorm(dot(u, ix["w_ik"]), ix["k_norm"], ix["k_bias"], 1e-6)
    return _rotary_halves(kI[:, :, None, :], yarn(cfg)[0])[:, :, 0]


def index_scores(ix, kI, u_rows, cq_rows, first, cfg, dot):
    """I [B,rows,S] (unmasked) of the queries at ``first`` ..: their HI
    heads' ReLU scores against every key, weighted and summed."""
    HI, dI = cfg["index_n_heads"], cfg["index_head_dim"]
    B, rows = u_rows.shape[:2]
    qI = _rotary_halves(dot(cq_rows, ix["w_iq"]).reshape(B, rows, HI, dI), yarn(cfg)[0], first)
    w = dot(u_rows, ix["w_iw"]) * (HI ** -0.5 * dI ** -0.5)
    z = jnp.einsum("bqjd,bsd->bqjs", qI, kI)
    return jnp.einsum("bqj,bqjs->bqs", w, jax.nn.relu(z))


def kl_rows(p, I, seen):
    """KL(p || softmax(I)) of every row over the keys it sees [B,rows]."""
    logq = jax.nn.log_softmax(jnp.where(seen, I, -jnp.inf), axis=-1)
    return jnp.sum(jnp.where(p > 0, p * (jnp.log(jnp.where(p > 0, p, 1.0))
                                        - jnp.where(seen, logq, 0.0)), 0.0), axis=-1)


def select(I, seen, topk):
    """The keys a query attends to in the model's published forward: of the
    keys it sees, the ``topk`` with the largest ``I`` (all, where it sees no
    more than that) [B,rows,S] bool."""
    masked = jnp.where(seen, I, -jnp.inf)
    if topk >= I.shape[-1]:
        return jnp.broadcast_to(seen, I.shape)
    kth = jnp.sort(masked, axis=-1)[..., -topk][..., None]
    return seen & (masked >= kth)


def mixer_block(w, ix, u, c_q, kv, k_r, first, rows, cfg, dot, sparse=False):
    """Queries ``first`` .. ``first + rows`` (``first`` may be traced: one
    compiled block serves every block of a length): (their rows of the attention's
    output before ``W_o`` [B,rows,H*dv], the sum of their rows' KL, the
    target p [B,rows,S]). ``kv`` [B,S,H,dn+dv] the expanded keys and values,
    ``k_r`` [B,S,1,dr] the shared rotary key, turned."""
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    B, S = u.shape[:2]
    inv_freq, factor, softmax_factor = yarn(cfg)
    u_rows, cq_rows = (jax.lax.dynamic_slice_in_dim(m, first, rows, 1) for m in (u, c_q))
    q = dot(cq_rows, w["w_uq"]).reshape(B, rows, -1, dn + dr)
    # the block's own positions: turn q's rotary part there
    ang = (first + jnp.arange(rows, dtype=jnp.float32))[:, None] * jnp.asarray(
        inv_freq, jnp.float32)
    cos, sin = (factor * f(ang)[None, :, None, :] for f in (jnp.cos, jnp.sin))
    a, b = q[..., dn::2], q[..., dn + 1::2]
    q_r = jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)
    s = (jnp.einsum("bqhd,bshd->bhqs", q[..., :dn], kv[..., :dn])
         + jnp.einsum("bqhd,bsd->bhqs", q_r, k_r[:, :, 0])) * (softmax_factor / np.sqrt(dn + dr))
    seen = jnp.arange(S)[None, :] <= (first + jnp.arange(rows))[:, None]  # [rows,S]
    I = index_scores(ix, index_keys(ix, u, cfg, dot), u_rows, cq_rows, first, cfg, dot)
    attend = select(I, seen, cfg["index_topk"])[:, None] if sparse else seen
    P = jax.nn.softmax(jnp.where(attend, s, -jnp.inf), axis=-1)  # [B,H,rows,S]
    p = jax.lax.stop_gradient(jnp.mean(P, axis=1))
    o = jnp.einsum("bhqs,bshd->bqhd", P, kv[..., dn:]).reshape(B, rows, -1)
    return o, jnp.sum(kl_rows(p, I, seen)), p


def mixed(w, ix, h, cfg, dot=jnp.matmul, sparse=False, **_):
    """A layer's first half: (``h + mla(n_in(h))``, ``L_layer``), block by
    block of queries; differentiable in ``ix``."""
    B, S = h.shape[:2]
    u = _rmsnorm(h, w["norm"], cfg["rms_norm_eps"])
    c_q, c, k_r = latents(u, w, cfg, dot)
    kv = dot(c, w["w_kvb"]).reshape(B, S, -1, cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
    outs, kl = [], 0.0
    for first in range(0, S, QUERY_BLOCK):
        rows = min(QUERY_BLOCK, S - first)
        o, k, _ = mixer_block(w, ix, u, c_q, kv, k_r, first, rows, cfg, dot, sparse)
        outs.append(o)
        kl = kl + k
    return h + dot(jnp.concatenate(outs, axis=1), w["wo"]), kl / (B * S)


def choose(scores, bias, cfg):
    """scores [T,E] (sigmoid, float32), bias [E] -> (the experts chosen
    [T,k], their gates [T,k], ``p_kth``, ``p_next`` [T] of ``scores +
    bias``: the k-th and (k+1)-th largest inside the kept groups, ``p_next``
    raised to ``p_kth`` times the best dropped group's score over the last
    kept one's where that is more: the nearer of the two ties)."""
    T, E = scores.shape
    k, groups, kept_n = cfg["num_experts_per_tok"], cfg["n_group"], cfg["topk_group"]
    if cfg["topk_method"] != "noaux_tc" or cfg["scoring_func"] != "sigmoid":
        raise ValueError(f"topk_method {cfg['topk_method']!r}, scoring_func "
                         f"{cfg['scoring_func']!r}")
    decide = scores + bias
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
    if kept_n < groups:
        p_n = jnp.maximum(p_n, p_k * (ranked[:, kept_n] / ranked[:, kept_n - 1]))
    return idx.astype(jnp.int32), gates, p_k, p_n


def fed(kind, w, bias, h, cfg, dot=jnp.matmul, router_dot=jnp.matmul, **_):
    """A layer's second half: ``h + ffn(n_ffn(h))`` -> (h, its routing or
    None)."""
    z = _rmsnorm(h, w["ffn_norm"], cfg["rms_norm_eps"])
    x = z.reshape(-1, z.shape[-1])
    if kind == "dense":
        y = _swiglu_blocks(x, w["w_gate"], w["w_up"], w["w_down"], dot)
        return h + y.reshape(h.shape), None
    first, held = cfg["deployment"]["experts_held"]
    scores = jax.nn.sigmoid(router_dot(x, w["router"]))
    idx, gates, p_k, p_n = choose(scores, bias, cfg)
    y = (_experts(x, gate_of(idx, gates, first, held), w, dot)
         + _swiglu(x, w["shared_gate"], w["shared_up"], w["shared_down"], dot))
    return h + y.reshape(h.shape), {"routing": idx, "p_kth": p_k, "p_next": p_n,
                                    "router_in": x}


def _f32(tree):
    return {k: jnp.asarray(v, jnp.float32) for k, v in tree.items()}


def _weights(params, cfg, i):
    """Layer ``i``: (the name of its run's stack, its index there, its
    weights, its indexer, its row of the selection bias or None), float32."""
    name, at = where(cfg)[i]
    routed = i - cfg["first_k_dense_replace"]
    return (name, at, _f32({k: v[at] for k, v in params["layers"][name].items()}),
            _f32({k: v[at] for k, v in params["indexer"][name].items()}),
            jnp.asarray(params["expert_bias"][routed], jnp.float32) if routed >= 0 else None)


def forward(params, tokens, cfg, **opts):
    """tokens int [B,S] -> (the last layer's output f32 [B,S,D], each
    layer's ``L_layer`` [layers], the expert layers' routing stacked over
    them), all at once (the tests' small sizes). ``dot`` / ``router_dot``:
    as ``reference_deepseek.forward``; ``sparse=True``: the published
    forward (the module's text)."""
    h, kls, routed = jnp.asarray(params["embed"], jnp.float32)[tokens], [], []
    for i, kind in enumerate(kinds(cfg)):
        _, _, w, ix, bias = _weights(params, cfg, i)
        h, kl = mixed(w, ix, h, cfg, **opts)
        h, r = fed(kind, w, bias, h, cfg, **opts)
        kls.append(kl)
        if r is not None:
            routed.append(r)
    return h, jnp.stack(kls), {k: jnp.stack([r[k] for r in routed]) for k in routed[0]}


def loss_of(indexer, params, tokens, cfg, **opts):
    """The stage's loss at once, differentiable in ``indexer`` (the tree
    under ``params["indexer"]``)."""
    return jnp.sum(forward({**params, "indexer": indexer}, tokens, cfg, **opts)[1])


def answers(params, tokens, cfg, positions, sample, **opts):
    """What the check compares: the last layer's output at ``positions`` of
    every sequence (under the name ``logits``: the place other cells give
    them), the loss, each layer's ``L_layer``, the global gradient norm (over
    the indexers' leaves: nothing else has a gradient), the sampled gradient
    leaves and the routing. ``params`` on the host in any dtype; half a layer
    at a time goes to the device in float32, the mixer's half a block of
    queries at a time with the gradient of that block's KL."""
    ks, dot = kinds(cfg), opts.get("dot", jnp.matmul)
    wanted = {p: {} for p in sample["grad_leaves"]}
    B, S = tokens.shape

    @jax.jit
    def prepare(w, h):
        u = _rmsnorm(h, w["norm"], cfg["rms_norm_eps"])
        c_q, c, k_r = latents(u, w, cfg, dot)
        return u, c_q, dot(c, w["w_kvb"]).reshape(
            B, S, -1, cfg["qk_nope_head_dim"] + cfg["v_head_dim"]), k_r

    def block(w, ix, u, c_q, kv, k_r, first, rows):
        def f(ix):
            o, kl, _ = mixer_block(w, ix, u, c_q, kv, k_r, first, rows, cfg, dot)
            return kl / (B * S), o
        (kl, o), g = jax.value_and_grad(f, has_aux=True)(ix)
        return o, kl, g

    block = jax.jit(block, static_argnums=7)  # ``first`` traced: ONE program a block length
    second = jax.jit(lambda kind, w, bias, h: fed(kind, w, bias, h, cfg, **opts),
                     static_argnums=0)
    with jax.default_matmul_precision("highest"):
        h = jnp.asarray(params["embed"], jnp.float32)[tokens]
        kls, routed, squares = [], [], 0.0
        for i, kind in enumerate(ks):
            name, at, w, ix, bias = _weights(params, cfg, i)
            mixer = {k: v for k, v in w.items() if k not in FFN_LEAVES}
            u, c_q, kv, k_r = prepare(mixer, h)
            outs, kl, grads = [], 0.0, None
            for first in range(0, S, QUERY_BLOCK):
                o, k, g = block(mixer, ix, u, c_q, kv, k_r, first, min(QUERY_BLOCK, S - first))
                outs.append(o)
                kl = kl + k
                grads = g if grads is None else jax.tree_util.tree_map(jnp.add, grads, g)
            h = h + dot(jnp.concatenate(outs, axis=1), mixer["wo"])
            del u, c_q, kv, k_r, outs, mixer
            kls.append(float(kl))
            for key, g in grads.items():
                squares = squares + float(jnp.sum(jnp.square(g)))
                path = f"indexer.{name}.{key}"
                if path in wanted:
                    leaf = params["indexer"][name][key]
                    wanted[path][at] = np.asarray(_sampled(
                        g.reshape(-1), leaf.size, sample["grad_elements"], at * g.size))
            h, r = second(kind, {k: v for k, v in w.items() if k in FFN_LEAVES}, bias, h)
            del w
            if r is not None:
                routed.append({k: np.asarray(v) for k, v in r.items()})
    missing = [p for p, got in wanted.items() if not got]
    if missing:
        raise KeyError(f"no gradient leaf {missing}")
    return {"logits": np.asarray(h[:, np.asarray(positions, int)]),
            "loss": float(sum(kls)), "layer_losses": np.asarray(kls, np.float32),
            "grad_norm": np.asarray(np.sqrt(squares)),
            **{"grad." + p: np.concatenate([got[at] for at in sorted(got)])
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
        sys.exit(f"chipbench/reference_deepseek_v32.py: no TPU ({jax.devices()[0].platform})")
    tokens, positions = check_sample(cfg, sample, cfg["recipe"]["seq_len"])
    # the program's own (bf16-rounded) weights, the trainable indexers and the
    # frozen tree, moved to the host: 2 bytes a parameter there, and the
    # device holds half a layer in float32 at a time
    params = jax.device_get({
        **jax.jit(lambda: init_(jax.random.PRNGKey(sample["seed"]), pc))(),
        **adapter.held(sample["seed"], pc)})
    np.savez(argv[2], platform=jax.devices()[0].platform,
             **answers(params, tokens, cfg, positions, sample))


if __name__ == "__main__":
    main(sys.argv[1:])
