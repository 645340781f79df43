"""What ``deepseek-v2.bare-mla-yarn``'s check reads on the chip, for the
program as it is and for the faults it has to refuse:

(a) ``group_best_two``: a group scored by the sum of its best two (Ling's
    rule) where the source scores it by its best;
(b) ``gates_renormalised``: the gates over their sum (``norm_topk_prob``);
(c) ``scaling_one``: ``routed_scaling_factor`` 1 for 16;
(d) ``no_mscale``: YaRN's ``mscale`` squared left out of the softmax scale;
(e) ``no_yarn``: the plain rotary frequencies for YaRN's;
(f) ``no_q_norm``, ``no_kv_norm``: either latent's RMSNorm left out;
(g) ``no_balance``: the sequence-wise balance term left out of the loss
    (value and gradient);
(h) ``no_shared``: the shared experts left out;
(i) ``fp8_experts``, ``fp8_latents``: the held experts' operands, or both
    latents, at three mantissa bits: the nearest precision below the
    payload's bf16;

for the balance term kept in the value but cut from the gradient
(``balance_no_gradient``: reported with what it reads), and for the two
controls of the router's precision (``router_three_passes``, ``bf16_router``:
bf16 where float32 is stated).

The check is the cell's own (``chipbench/jobs/bare_routed.py`` against
``reference_deepseek.py``'s answers on the fixed sample, at the published
widths, the cut's five layers, one sequence of 16,384); the faults are put
into ``torchft_tpu/`` from here, the program has no switch for them, and the
CPU tests put the same ones in at a small size.

    chiprun -- python3 benchmarks/deepseek_check_faults.py [workload [variant ...]]

One JSON line per variant; exits 2 without a TPU. With ``share_room [seed
...]`` for a variant it prints instead, for eight seeds of fresh weights and
tokens (or for the weights and tokens the cell's run draws from each
``--seed`` given), each expert layer's pairs that reach the held experts over
the even share (what ``deployment.share_room`` is sized from, and what a
run's rate follows); with ``lr <rate> ...`` it runs the
cell's fused AdamW step six times at each rate and prints what the held
experts' load does (what ``recipe.lr`` is chosen from).
"""

import dataclasses
import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import manifest  # noqa: E402

_spec = importlib.util.spec_from_file_location(
    "ling_check_faults", os.path.join(ROOT, "benchmarks", "ling_check_faults.py"))
_ling = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_ling)
_patched, _fp8_like, reading = _ling._patched, _ling._fp8_like, _ling.reading


def _faults(pc):
    """``pc``: the program's config object (the latents' widths tell their
    norms from the others)."""
    import jax

    from torchft_tpu.models import deepseek, mellum, mla, moe

    within, choose, gmm, ffn = (moe._within_groups, moe._choose, moe._grouped_matmul,
                                deepseek.moe_ffn)
    norm, mixer, balance = mla._rmsnorm, deepseek.mla_mixer, moe.sequence_balance_loss

    def chosen_as(**changed):
        return lambda s, cfg, routing, bias=None: choose(
            s, dataclasses.replace(cfg, **changed), routing, bias)

    def norm_but(width, then=lambda x: x):
        return lambda x, w, eps: then(x) if x.shape[-1] == width else norm(x, w, eps)

    return {
        "group_best_two": lambda: _patched(
            moe, "_within_groups", lambda decide, cfg: within(
                decide, dataclasses.replace(cfg, topk_method="noaux_tc"))),
        "gates_renormalised": lambda: _patched(
            moe, "_choose", chosen_as(norm_topk_prob=True)),
        "scaling_one": lambda: _patched(moe, "_choose", chosen_as(routed_scaling=1.0)),
        "no_mscale": lambda: _patched(
            deepseek, "mla_mixer", lambda u, w, cfg, attention, rotate, heads, factor: mixer(
                u, w, cfg, attention, rotate, heads, 1.0)),
        "no_yarn": lambda: _patched(deepseek, "yarn_inv_freq", mellum._plain_inv_freq),
        "no_q_norm": lambda: _patched(mla, "_rmsnorm", norm_but(pc.q_lora_rank)),
        "no_kv_norm": lambda: _patched(mla, "_rmsnorm", norm_but(pc.kv_lora_rank)),
        "no_balance": lambda: _patched(
            moe, "sequence_balance_loss", lambda probs, idx: 0.0 * balance(probs, idx)),
        "balance_no_gradient": lambda: _patched(
            moe, "sequence_balance_loss",
            lambda probs, idx: jax.lax.stop_gradient(balance(probs, idx))),
        "no_shared": lambda: _patched(
            deepseek, "moe_ffn", lambda *a, shared=None, **kw: ffn(*a, **kw)),
        "fp8_experts": lambda: _patched(
            moe, "_grouped_matmul", lambda rows, w, sizes: gmm(
                _fp8_like(rows), _fp8_like(w), sizes)),
        "fp8_latents": lambda: _patched(
            mla, "_rmsnorm", lambda x, w, eps: _fp8_like(norm(x, w, eps))
            if x.shape[-1] in (pc.q_lora_rank, pc.kv_lora_rank) else norm(x, w, eps)),
        "router_three_passes": lambda: _patched(
            moe, "ROUTER_PRECISION", jax.lax.Precision.HIGH),
        "bf16_router": lambda: _patched(
            moe, "ROUTER_PRECISION", jax.lax.Precision.DEFAULT),
    }


def fault(name, pc):
    """A context in which the program has the fault ``name`` (a key of
    :func:`_faults`); compiled functions made outside it do not."""
    return _faults(pc)[name]()


FAULTS = ("group_best_two", "gates_renormalised", "scaling_one", "no_mscale", "no_yarn",
          "no_q_norm", "no_kv_norm", "no_balance", "no_shared", "fp8_experts", "fp8_latents")
REPORTED = ("balance_no_gradient",)
CONTROLS = ("router_three_passes", "bf16_router")


def share_room(adapter, cfg, seeds=()):
    """For each of ``seeds`` (none: eight of this script's own), weights and
    tokens as ``jobs/bare_routed.py`` draws them from a run's ``--seed``:
    each expert layer's pairs that reach the held experts over the even
    share."""
    import jax
    import numpy as np

    from torchft_tpu.models.deepseek import deepseek_hidden

    pc = dataclasses.replace(adapter.config(cfg), share_room=16.0)  # every pair counted
    B, S = cfg["recipe"]["batch_size"], cfg["recipe"]["seq_len"]
    even = B * S * pc.top_k * pc.n_held / pc.num_experts

    @jax.jit
    def held(seed, token_seed):
        params = adapter.program()[0](jax.random.PRNGKey(seed), pc)
        tokens = jax.random.randint(jax.random.PRNGKey(token_seed), (B, S), 0, pc.vocab_size)
        return deepseek_hidden(params, tokens, pc)[1]["held_pairs"]

    for seed in seeds or [1000 * i + 7 for i in range(8)]:
        got = np.asarray(held(seed % 2**31, (seed + 1) % 2**31)) / even
        print(json.dumps({"share_room_seed": seed, "held_over_even": [
            round(float(x), 4) for x in got]}), flush=True)


def learning_rates(adapter, cfg, rates, steps=6):
    """The cell's fused, donated AdamW step ``steps`` times at each of
    ``rates`` from one seed: the loss, the balance term and the held
    experts' load step by step."""
    import jax
    import optax

    init_, loss_, _ = adapter.program()
    recipe = cfg["recipe"]
    pc = dataclasses.replace(adapter.config(cfg), share_room=16.0)  # every pair counted
    B, S = recipe["batch_size"], recipe["seq_len"]
    tokens = jax.random.randint(jax.random.PRNGKey(8), (B, S), 0, pc.vocab_size)
    for rate in rates:
        tx = optax.adamw(rate, weight_decay=recipe["weight_decay"])

        def step(params, opt_state, tokens):
            (loss, stats), grads = jax.value_and_grad(
                lambda p: loss_(p, tokens, tokens, pc, remat=recipe["remat"], with_stats=True),
                has_aux=True)(params)
            updates, opt_state = tx.update(grads, opt_state, params)
            keep = ("aux_loss", "held_pair_share", "load_max_over_mean", "overflow_pairs")
            return (optax.apply_updates(params, updates), opt_state, loss,
                    {k: stats[k] for k in keep})

        jstep = jax.jit(step, donate_argnums=(0, 1))
        params = jax.jit(lambda: init_(jax.random.PRNGKey(7), pc))()
        opt_state = jax.jit(tx.init)(params)
        for i in range(steps):
            params, opt_state, loss, stats = jstep(params, opt_state, tokens)
            print(json.dumps({"lr": rate, "step": i, "loss": float(loss),
                              **{k: float(v) for k, v in stats.items()}}), flush=True)
        del params, opt_state
        jax.clear_caches()


def main(argv):
    bench = manifest.load(ROOT)
    cell = manifest.Cell(ROOT, bench, argv[0] if argv else "deepseek-v2.bare-mla-yarn")
    job, adapter = cell.job(), cell.adapter()
    cfg, seq = cell.config, cell.config["recipe"]["seq_len"]
    sample = job.check_sample_of(cell, adapter)
    if argv[1:2] in (["share_room"], ["lr"]):
        import jax

        if jax.devices()[0].platform != "tpu":
            return 2
        if argv[1] == "share_room":
            share_room(adapter, cfg, [int(x) for x in argv[2:]])
        else:
            learning_rates(adapter, cfg, [float(x) for x in argv[2:]])
        return 0
    # a child computes the reference's answers before this process takes the chip
    ref = job._reference_answers(cell, adapter, sample,
                                 os.path.join(ROOT, ".chipbench_cache"))

    import jax

    if jax.devices()[0].platform != "tpu":
        return 2
    check, pc = cell.traffic["check"], adapter.config(cfg)

    def show(name, **kw):
        jax.clear_caches()
        got = reading(job, adapter, cfg, sample, seq, ref, check, **kw)
        print(json.dumps({"variant": name, **got}), flush=True)

    show("program", free=True)
    for name in argv[1:] or FAULTS + REPORTED + CONTROLS:
        with fault(name, pc):
            show(name, router_only=name in CONTROLS)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
