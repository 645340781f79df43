"""The plain reference: a decoder-only transformer (RMSNorm, rotary GQA
attention, SwiGLU, untied head) forward and backward in straightforward
float32 ``jax.numpy`` — no kernels, no remat, no scan, matmuls at "highest"
precision (a TPU runs f32 matmuls in bf16 passes otherwise). It follows the
published Mistral / InternLM2 modelling code: pre-norm residual blocks,
``rotate_half`` rotary on the first and second half of each head, softmax in
f32 over a causal mask, ``down(silu(gate(x)) * up(x))``. Departures: none for
Mistral-7B-v0.3 (no sliding window); InternLM2's fused ``wqkv`` is three
separate matrices here (the same equations). It shares no code with the
program; it reads the configuration file's Hugging Face keys.

The parameter tree has the program's layout (``llama_init``) so that both
sides can be given the same seeded weights: embed [V,D], lm_head [D,V],
final_norm [D], layers.* stacked on a leading depth axis.

As a script (a child of the ``bare`` job, which may not touch JAX while this
holds the chip) it writes the reference's answers for the check sample:

    python3 chipbench/reference.py <config.json> <sample.json> <out.npz>
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


def forward(params, tokens, cfg, dot=jnp.matmul):
    """tokens int [B,S] -> logits f32 [B,S,V]. ``dot`` multiplies activations
    by a weight matrix; the tests pass one whose backward pass is in a lower
    precision to show that the check refuses it."""
    hq, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    B, S = tokens.shape
    causal = jnp.tril(jnp.ones((S, S), bool))
    h = params["embed"][tokens]
    L = params["layers"]
    for i in range(cfg["num_hidden_layers"]):
        x = _rmsnorm(h, L["attn_norm"][i], eps)
        q = _rotary(dot(x, L["wq"][i]).reshape(B, S, hq, hd), theta)
        k = _rotary(dot(x, L["wk"][i]).reshape(B, S, hkv, hd), theta)
        v = dot(x, L["wv"][i]).reshape(B, S, hkv, hd)
        k = jnp.repeat(k, hq // hkv, axis=2)  # query head j reads kv head j // g
        v = jnp.repeat(v, hq // hkv, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        a = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(B, S, hq * hd)
        h = h + dot(a, L["wo"][i])
        x = _rmsnorm(h, L["ffn_norm"][i], eps)
        h = h + dot(jax.nn.silu(dot(x, L["w_gate"][i])) * dot(x, L["w_up"][i]),
                    L["w_down"][i])
    return dot(_rmsnorm(h, params["final_norm"], eps), params["lm_head"])


def loss(logits, targets):
    """Mean cross-entropy of logits[b, s] against targets[b, s]."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))


def grad_answers(grads, sample):
    """Both sides' gradients as the check compares them: the global norm,
    and of each leaf named in ``sample["grad_leaves"]`` (a path in the
    parameter tree, "layers.wq": all layers of the stacked leaf) every k-th
    element, k chosen so that at most ``grad_elements`` leave the chip (a
    leaf at published widths is up to 1 GB in f32)."""
    out = {"grad_norm": jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                                     for g in jax.tree_util.tree_leaves(grads)))}
    for path in sample["grad_leaves"]:
        g = grads
        for key in path.split("."):
            g = g[key]
        every = -(-g.size // sample["grad_elements"])
        out["grad." + path] = g.reshape(-1)[::every].astype(jnp.float32)
    return out


def answers(params, tokens, cfg, positions, sample, dot=jnp.matmul):
    """What the check compares: logits at ``positions`` of every sequence,
    the loss (targets = tokens, as the trainer feeds them), the global
    gradient norm and the sampled gradient leaves. ``params`` in any dtype;
    computed in f32."""
    p32 = jax.tree_util.tree_map(
        lambda x: x if x.dtype == jnp.float32 else x.astype(jnp.float32), params)

    def both(p):
        logits = forward(p, tokens, cfg, dot)
        return loss(logits, tokens), logits[:, positions]

    @jax.jit
    def run(p):
        (val, logits), grads = jax.value_and_grad(both, has_aux=True)(p)
        return val, logits, grad_answers(grads, sample)  # the rest stays on the chip

    with jax.default_matmul_precision("highest"):
        val, logits, grads = run(p32)
    return {"logits": np.asarray(logits), "loss": float(val),
            **{k: np.asarray(v) for k, v in grads.items()}}


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
    from chipbench.worker import llama_config  # program's init, for equal weights
    from torchft_tpu.models.llama import llama_init

    if jax.devices()[0].platform != "tpu":  # before any work: no CPU answers
        sys.exit(f"chipbench/reference.py: no TPU ({jax.devices()[0].platform})")
    with open(argv[0]) as f, open(argv[1]) as g:
        cfg, sample = json.load(f), json.load(g)
    tokens, positions = check_sample(cfg, sample, cfg["recipe"]["seq_len"])
    # the program's bf16-rounded weights, upcast in the same call: the bf16
    # copy does not stay beside 4 bytes a parameter of weights and gradients
    params = jax.jit(lambda: jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32),
        llama_init(jax.random.PRNGKey(sample["seed"]), llama_config(cfg))))()
    np.savez(argv[2], platform=jax.devices()[0].platform,
             **answers(params, tokens, cfg, positions, sample))


if __name__ == "__main__":
    main(sys.argv[1:])
