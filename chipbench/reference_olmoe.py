"""The plain reference of OLMoE's decoder (allenai/OLMoE-1B-7B): forward,
loss and gradients in straightforward float32 ``jax.numpy`` — no kernels, no
sort, no scan, no remat of layers, matmuls at "highest" precision (a TPU runs
f32 matmuls in bf16 passes otherwise). It follows ``transformers``' published
``models/olmoe/modeling_olmoe.py``: pre-norm residual blocks; ``q_norm`` and
``k_norm`` are RMSNorms over the WHOLE projection, applied before the split
into heads; ``rotate_half`` rotary; softmax attention in f32 over a causal
mask; the router's softmax over all experts in f32, ``top_k`` of it, the
gates NOT renormalised unless ``norm_topk_prob``; every chosen expert's
``down(silu(gate(x)) * up(x))`` weighted by its gate and summed, no token
dropped; loss = cross-entropy + ``router_aux_loss_coef`` x
``load_balancing_loss_func`` (the layers' router outputs concatenated, every
one of the k choices counted).

Departures from that file, each without effect on the values: every expert
is computed on ALL tokens and weighted by the token's gate for that expert
(zero where it was not chosen), where the published code gathers each
expert's tokens (the same sum; a masked dense product holds no index
arithmetic that could share a fault with the program's sort); the experts
are taken 16 at a time, one batched product each for gate, up and down,
rematerialised in the backward pass (``jax.checkpoint`` around the 16:
[T, 64, 1024] intermediates a layer, kept for the backward pass, would not
fit beside 8.4 GB of float32 weights and gradients; a Python loop over 64
single experts compiled for five minutes on the chip); no attention
mask or padding (the sample has none); ``clip_qkv`` is null in the published
configuration and absent here. It shares no code with the program; it reads
the configuration file's Hugging Face keys.

The parameter tree has the program's layout (``moe_init``) so that both
sides can be given the same seeded weights: embed [V,D], lm_head [D,V],
final_norm [D], layers.* stacked on a leading depth axis, experts on a
second: w_gate / w_up [L,E,D,H], w_down [L,E,H,D], router [L,D,E].

Besides its answers it hands out its routing: per layer and token the k
experts it chose and the probabilities of its k-th and (k+1)-th choice, so
that a check can replay the first and knows from the second how near a tie
each decision was; and what each layer's router was given (``router_in``
[L,T,D] float32), so that a check can put the program's router before the
very same input and see its precision alone.

As a script (a child of the ``bare_routed`` job, which may not touch JAX
while this holds the chip):

    python3 chipbench/reference_olmoe.py <config.json> <sample.json> <out.npz>
"""

import json
import sys

import jax
import jax.numpy as jnp
import numpy as np


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


EXPERTS_AT_ONCE = 16


def _experts(x, weight_of, w_gate, w_up, w_down, dot):
    """x [T,D]; weight_of [T,E]: a token's gate for each expert, zero where
    the expert was not chosen -> sum over experts of gate * expert(x). Every
    expert on every token, ``EXPERTS_AT_ONCE`` experts a time (one batched
    product each for gate, up and down: [T, 16, 1024] intermediates, not
    [T, 64, 1024]), rematerialised in the backward pass."""
    @jax.checkpoint
    def some(x, wg, wu, wd, w):  # wg, wu [e,D,H]; wd [e,H,D]; w [T,e]
        h = jax.nn.silu(dot(x, wg)) * dot(x, wu)  # [e,T,H]
        return jnp.sum(jnp.swapaxes(w, 0, 1)[..., None] * dot(h, wd), axis=0)

    y = jnp.zeros_like(x)
    for e in range(0, w_gate.shape[0], EXPERTS_AT_ONCE):
        at = slice(e, e + EXPERTS_AT_ONCE)
        y = y + some(x, w_gate[at], w_up[at], w_down[at], weight_of[:, at])
    return y


def forward(params, tokens, cfg, dot=jnp.matmul, router_dot=jnp.matmul,
            expert_dot=None):
    """tokens int [B,S] -> (logits f32 [B,S,V], the load-balancing loss, the
    routing: ``routing`` [L,T,k] int32, ``p_kth`` and ``p_next`` [L,T],
    ``router_in`` [L,T,D]).
    ``dot`` multiplies activations by a weight matrix, ``router_dot`` by the
    router's, ``expert_dot`` (absent: ``dot``) by an expert's; the tests pass
    ones of a lower precision to show that the check refuses them."""
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // hq
    E, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    B, S = tokens.shape
    causal = jnp.tril(jnp.ones((S, S), bool))
    h = params["embed"][tokens]
    L = params["layers"]
    chosen, p_kth, p_next, all_probs, router_in = [], [], [], [], []
    for i in range(cfg["num_hidden_layers"]):
        x = _rmsnorm(h, L["attn_norm"][i], eps)
        q = _rmsnorm(dot(x, L["wq"][i]), L["q_norm"][i], eps)  # whole projection
        kk = _rmsnorm(dot(x, L["wk"][i]), L["k_norm"][i], eps)
        q = _rotary(q.reshape(B, S, hq, hd), theta)
        kk = _rotary(kk.reshape(B, S, hkv, hd), theta)
        v = dot(x, L["wv"][i]).reshape(B, S, hkv, hd)
        kk = jnp.repeat(kk, hq // hkv, axis=2)
        v = jnp.repeat(v, hq // hkv, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / np.sqrt(hd)
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        a = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(B, S, hq * hd)
        h = h + dot(a, L["wo"][i])
        x = _rmsnorm(h, L["ffn_norm"][i], eps).reshape(B * S, -1)
        probs = jax.nn.softmax(router_dot(x, L["router"][i]), axis=-1)  # [T,E]
        top_p, top_i = jax.lax.top_k(probs, min(k + 1, E))
        gates, idx = top_p[:, :k], top_i[:, :k]
        if cfg["norm_topk_prob"]:
            gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
        weight_of = jnp.sum(jax.nn.one_hot(idx, E) * gates[..., None], axis=1)
        y = _experts(x, weight_of, L["w_gate"][i], L["w_up"][i], L["w_down"][i],
                     expert_dot or dot)
        h = h + y.reshape(B, S, -1)
        chosen.append(idx)
        p_kth.append(top_p[:, k - 1])
        p_next.append(top_p[:, -1])
        all_probs.append(probs)
        router_in.append(x)
    logits = dot(_rmsnorm(h, params["final_norm"], eps), params["lm_head"])
    # load_balancing_loss_func: gate outputs of all layers concatenated
    probs = jnp.concatenate(all_probs, axis=0)  # [L*T,E]
    mask = jax.nn.one_hot(jnp.concatenate(chosen, axis=0), E)  # [L*T,k,E]
    tokens_per_expert = jnp.mean(mask, axis=0)  # [k,E]
    prob_per_expert = jnp.mean(probs, axis=0)  # [E]
    aux = E * jnp.sum(tokens_per_expert * prob_per_expert[None, :])
    routing = {"routing": jnp.stack(chosen).astype(jnp.int32),
               "p_kth": jnp.stack(p_kth), "p_next": jnp.stack(p_next),
               "router_in": jax.lax.stop_gradient(jnp.stack(router_in))}
    return logits, aux, routing


def loss(logits, targets, aux, cfg):
    """Mean cross-entropy of logits[b, s] against targets[b, s], plus the
    weighted load-balancing loss."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))
    return ce + cfg["router_aux_loss_coef"] * aux


def grad_answers(grads, sample):
    """Both sides' gradients as the check compares them: the global norm,
    and of each leaf named in ``sample["grad_leaves"]`` (a path in the
    parameter tree) every k-th element, k chosen so that at most
    ``grad_elements`` leave the chip."""
    out = {"grad_norm": jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                                     for g in jax.tree_util.tree_leaves(grads)))}
    for path in sample["grad_leaves"]:
        g = grads
        for key in path.split("."):
            g = g[key]
        every = -(-g.size // sample["grad_elements"])
        out["grad." + path] = g.reshape(-1)[::every].astype(jnp.float32)
    return out


def answers(params, tokens, cfg, positions, sample, **dots):
    """What the check compares: logits at ``positions`` of every sequence,
    the loss (targets = tokens, as the trainer feeds them), the
    load-balancing loss alone, the global gradient norm, the sampled
    gradient leaves, and the routing. ``params`` in any dtype; computed in
    f32."""
    p32 = jax.tree_util.tree_map(
        lambda x: x if x.dtype == jnp.float32 else x.astype(jnp.float32), params)

    def both(p):
        logits, aux, routing = forward(p, tokens, cfg, **dots)
        return loss(logits, tokens, aux, cfg), (logits[:, positions], aux, routing)

    @jax.jit
    def run(p):
        (val, rest), grads = jax.value_and_grad(both, has_aux=True)(p)
        return val, rest, grad_answers(grads, sample)  # the rest stays on the chip

    with jax.default_matmul_precision("highest"):
        val, (logits, aux, routing), grads = run(p32)
    return {"logits": np.asarray(logits), "loss": float(val), "aux_loss": float(aux),
            **{k: np.asarray(v) for k, v in grads.items()},
            **{k: np.asarray(v) for k, v in routing.items()}}


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
        sys.exit(f"chipbench/reference_olmoe.py: no TPU ({jax.devices()[0].platform})")
    tokens, positions = check_sample(cfg, sample, cfg["recipe"]["seq_len"])
    # the program's bf16-rounded weights, upcast in the same call: the bf16
    # copy does not stay beside 4 bytes a parameter of weights and gradients
    params = jax.jit(lambda: jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32),
        init_(jax.random.PRNGKey(sample["seed"]), pc)))()
    np.savez(argv[2], platform=jax.devices()[0].platform,
             **answers(params, tokens, cfg, positions, sample))


if __name__ == "__main__":
    main(sys.argv[1:])
