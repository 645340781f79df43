"""Numerical parity of the in-tree MoE against HuggingFace Mixtral.

The MoE family has no counterpart in the reference framework (SURVEY.md
§2.4: EP absent), so its correctness anchor is the public architecture it
implements: Mixtral — Llama attention + top-k routed SwiGLU experts with
the gates renormalized over the selected experts. Our GShard-style
capacity dispatch is an *execution strategy* (static shapes for the MXU),
not a different function: with capacity >= tokens nothing ever drops, and
the layer must compute exactly Mixtral's expert mixture. This test maps one
set of random weights into both models and asserts the logits agree in
fp32. A routing bug (wrong gate normalization, slot collision, expert
permutation) shows up here as a gross mismatch, not noise.
"""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

pytestmark = pytest.mark.slow  # loads torch+transformers (tens of seconds)

jax = pytest.importorskip("jax")
torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

import jax.numpy as jnp  # noqa: E402

from torchft_tpu.models.moe import (  # noqa: E402
    MOE_CONFIGS,
    moe_forward,
    moe_init,
)

# capacity_factor = E/k makes capacity == token count: nothing can overflow,
# so the capacity-dispatch path must equal Mixtral's dropless routing.
CFG = dataclasses.replace(
    MOE_CONFIGS["debug"],
    rope_theta=10000.0,
    capacity_factor=MOE_CONFIGS["debug"].num_experts
    / MOE_CONFIGS["debug"].top_k,
)


def _hf_model(params) -> "transformers.MixtralForCausalLM":
    hf_cfg = transformers.MixtralConfig(
        vocab_size=CFG.vocab_size,
        hidden_size=CFG.dim,
        intermediate_size=CFG.ffn_hidden,
        num_hidden_layers=CFG.n_layers,
        num_attention_heads=CFG.n_heads,
        num_key_value_heads=CFG.n_kv_heads,
        max_position_embeddings=CFG.max_seq_len,
        rms_norm_eps=CFG.norm_eps,
        rope_theta=CFG.rope_theta,
        num_local_experts=CFG.num_experts,
        num_experts_per_tok=CFG.top_k,
        tie_word_embeddings=False,
        attn_implementation="eager",
    )
    model = transformers.MixtralForCausalLM(hf_cfg)
    model.eval()

    def t(x) -> torch.Tensor:
        return torch.from_numpy(np.asarray(x, dtype=np.float32))

    layers = params["layers"]
    with torch.no_grad():
        model.model.embed_tokens.weight.copy_(t(params["embed"]))
        model.model.norm.weight.copy_(t(params["final_norm"]))
        model.lm_head.weight.copy_(t(params["lm_head"]).T)
        for i, layer in enumerate(model.model.layers):
            layer.input_layernorm.weight.copy_(t(layers["attn_norm"][i]))
            layer.post_attention_layernorm.weight.copy_(
                t(layers["ffn_norm"][i])
            )
            layer.self_attn.q_proj.weight.copy_(t(layers["wq"][i]).T)
            layer.self_attn.k_proj.weight.copy_(t(layers["wk"][i]).T)
            layer.self_attn.v_proj.weight.copy_(t(layers["wv"][i]).T)
            layer.self_attn.o_proj.weight.copy_(t(layers["wo"][i]).T)
            moe = layer.block_sparse_moe
            moe.gate.weight.copy_(t(layers["router"][i]).T)
            for e, expert in enumerate(moe.experts):
                expert.w1.weight.copy_(t(layers["w_gate"][i][e]).T)  # gate
                expert.w3.weight.copy_(t(layers["w_up"][i][e]).T)  # up
                expert.w2.weight.copy_(t(layers["w_down"][i][e]).T)  # down
    return model


def test_logits_match_mixtral():
    params = moe_init(jax.random.PRNGKey(0), CFG)
    model = _hf_model(params)

    tokens = np.asarray(
        jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0, CFG.vocab_size)
    )

    ours, _aux, _stats = moe_forward(
        params, jnp.asarray(tokens), CFG, remat="none"
    )
    ours = np.asarray(ours)
    with torch.no_grad():
        theirs = model(torch.from_numpy(tokens)).logits.numpy()

    assert ours.shape == theirs.shape
    np.testing.assert_allclose(ours, theirs, atol=3e-4, rtol=2e-3)


# ---- OLMoE (PR 28): the dropless block against transformers' published
# modelling code, ``OlmoeForCausalLM``: top-k of a softmax over all experts
# with the gates NOT renormalised, RMSNorm over the whole q and k
# projections, every routed token computed, and ``load_balancing_loss_func``
# over the layers' concatenated router outputs.

OLMOE = dataclasses.replace(
    MOE_CONFIGS["debug"], rope_theta=10000.0, n_kv_heads=4, num_experts=8,
    top_k=4, capacity_factor=None, norm_topk_prob=False, qk_norm=True,
)


def _hf_olmoe(params) -> "transformers.OlmoeForCausalLM":
    hf_cfg = transformers.OlmoeConfig(
        vocab_size=OLMOE.vocab_size, hidden_size=OLMOE.dim,
        intermediate_size=OLMOE.ffn_hidden, num_hidden_layers=OLMOE.n_layers,
        num_attention_heads=OLMOE.n_heads, num_key_value_heads=OLMOE.n_kv_heads,
        max_position_embeddings=OLMOE.max_seq_len, rms_norm_eps=OLMOE.norm_eps,
        rope_theta=OLMOE.rope_theta, num_experts=OLMOE.num_experts,
        num_experts_per_tok=OLMOE.top_k, norm_topk_prob=False,
        router_aux_loss_coef=OLMOE.aux_loss_weight, output_router_logits=True,
        tie_word_embeddings=False, attn_implementation="eager",
    )
    model = transformers.OlmoeForCausalLM(hf_cfg)
    model.eval()

    def t(x) -> torch.Tensor:
        return torch.from_numpy(np.asarray(x, dtype=np.float32))

    layers = params["layers"]
    with torch.no_grad():
        model.model.embed_tokens.weight.copy_(t(params["embed"]))
        model.model.norm.weight.copy_(t(params["final_norm"]))
        model.lm_head.weight.copy_(t(params["lm_head"]).T)
        for i, layer in enumerate(model.model.layers):
            layer.input_layernorm.weight.copy_(t(layers["attn_norm"][i]))
            layer.post_attention_layernorm.weight.copy_(t(layers["ffn_norm"][i]))
            att = layer.self_attn
            att.q_proj.weight.copy_(t(layers["wq"][i]).T)
            att.k_proj.weight.copy_(t(layers["wk"][i]).T)
            att.v_proj.weight.copy_(t(layers["wv"][i]).T)
            att.o_proj.weight.copy_(t(layers["wo"][i]).T)
            att.q_norm.weight.copy_(t(layers["q_norm"][i]))
            att.k_norm.weight.copy_(t(layers["k_norm"][i]))
            layer.mlp.gate.weight.copy_(t(layers["router"][i]).T)
            for e, expert in enumerate(layer.mlp.experts):
                expert.gate_proj.weight.copy_(t(layers["w_gate"][i][e]).T)
                expert.up_proj.weight.copy_(t(layers["w_up"][i][e]).T)
                expert.down_proj.weight.copy_(t(layers["w_down"][i][e]).T)
    return model


def test_logits_and_auxiliary_loss_match_olmoe():
    params = moe_init(jax.random.PRNGKey(0), OLMOE)
    # norms that are not all ones, so that a norm left out would show
    key = jax.random.PRNGKey(7)
    for name in ("q_norm", "k_norm"):
        key, sub = jax.random.split(key)
        params["layers"][name] = 1.0 + 0.3 * jax.random.normal(
            sub, params["layers"][name].shape)
    model = _hf_olmoe(params)
    tokens = np.asarray(
        jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0, OLMOE.vocab_size)
    )
    ours, aux, _ = moe_forward(params, jnp.asarray(tokens), OLMOE, remat="none")
    with torch.no_grad():
        ids = torch.from_numpy(tokens).long()
        out = model(ids, labels=ids)
    theirs = out.logits.numpy()
    err = float(np.abs(np.asarray(ours) - theirs).max())
    print(f"OLMoE parity: max |logit diff| {err:.3e}, aux ours {float(aux):.6f} "
          f"theirs {float(out.aux_loss):.6f}")
    np.testing.assert_allclose(np.asarray(ours), theirs, atol=3e-4, rtol=2e-3)
    assert float(aux) == pytest.approx(float(out.aux_loss), rel=1e-5)
