"""What ``deepseek-v3.2-exp.bare-dsa-warmup-16k``'s check reads, for the
program as it is and for the faults it has to refuse:

(a) ``p_bf16``, ``i_bf16``: the target, or the indexer's block of scores,
    rounded to bf16 before the logarithm and the exponential: the nearest
    precision below the float32 the recipe states for them; ``fp8_indexer``:
    the indexer's queries and key at three mantissa bits: the nearest
    precision below the bf16 the recipe states for those;
(b) ``no_relu``: the indexer's ReLU left out;
(c) ``target_one_head``: the target from head 0 alone;
(d) ``rmsnorm_for_layernorm``: an RMSNorm where the indexer's key has a
    LayerNorm;
(e) ``no_head_factor``: the indexer's weights without ``index_n_heads^-0.5``;
(f) ``no_rotary_on_key``: the indexer's key not turned;
(g) ``group_best_one``: a group scored by its best expert where the source
    scores it by its best two;

and for the two controls of the router's precision (``router_three_passes``,
``bf16_router``: bf16 where float32 is stated; part C alone).

The check is the cell's own (``chipbench/jobs/bare_frozen.py`` against
``reference_deepseek_v32.py``'s answers on the fixed sample, at the published
widths, the cut's five layers, one sequence of 16,384); the faults are put
into ``torchft_tpu/`` from here (``ops/dsa.py`` keeps four values for it that
the program never changes), and the CPU tests put the same ones in at a
small size. The frozen tree is made once for all variants.

    chiprun -- python3 benchmarks/dsa_check_faults.py [workload [variant ...]]

One JSON line per variant; exits 2 without a TPU. With ``loads [seed ...]``
for a variant it prints instead, for eight seeds of fresh weights and tokens
(or for the weights and tokens the cell's run draws from each ``--seed``
given), each expert layer's pairs that reach the held experts over the even
share (what ``deployment.share_room`` is sized from) and the stage's
counters (every layer's KL, ``dsa_topk_mass``). With ``cpu`` for the
workload it runs the same variants here at tiny widths in float32 against
float32 limits (the reference computed in this process): what the tests
assert, as a script.
"""

import dataclasses
import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import manifest  # noqa: E402

CELL = "deepseek-v3.2-exp.bare-dsa-warmup-16k"
# the cell's configuration at widths a CPU runs (``cpu``), and the limits a
# float32 program is held to there: tests/chipbench/test_rehearsal_deepseek_v32.py
# takes both from here
TINY = dict(hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
            num_attention_heads=8, num_key_value_heads=8, q_lora_rank=24, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, vocab_size=512,
            n_routed_experts=4, num_experts_per_tok=4, n_group=4, topk_group=2,
            index_n_heads=4, index_head_dim=16, index_topk=16)
F32 = {"tolerances": {"logits_rel": 1e-4, "loss_abs": 2e-5, "grad_norm_rel": 5e-5,
                      "grad_leaf_rel": 5e-4, "layer_loss_rel": 5e-5},
       "routing": {"max_share": 0.0, "max_margin": 0.0}, "router": {"max_prob_rel": 1e-5}}


_spec = importlib.util.spec_from_file_location(
    "ling_check_faults", os.path.join(ROOT, "benchmarks", "ling_check_faults.py"))
_ling = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_ling)
_patched, _fp8_like = _ling._patched, _ling._fp8_like


def _faults(pc):
    import jax
    import jax.numpy as jnp

    from torchft_tpu.models import dsa, moe
    from torchft_tpu.ops import dsa as kernels

    indexer, turn, within = dsa.indexer, dsa._turn_first, moe._within_groups

    def rms(x, weight, bias, eps):
        x = x.astype(jnp.float32)
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight

    def unscaled(ix, u, cq, cfg, table):
        qI, kI, w = indexer(ix, u, cq, cfg, table)
        return qI, kI, w * cfg.index_n_heads ** 0.5

    def fp8(ix, u, cq, cfg, table):
        qI, kI, w = indexer(ix, u, cq, cfg, table)
        return _fp8_like(qI), _fp8_like(kI), w

    return {
        "p_bf16": lambda: _patched(kernels, "P_DTYPE", jnp.bfloat16),
        "i_bf16": lambda: _patched(kernels, "I_DTYPE", jnp.bfloat16),
        "fp8_indexer": lambda: _patched(dsa, "indexer", fp8),
        "no_relu": lambda: _patched(kernels, "RELU", False),
        "target_one_head": lambda: _patched(kernels, "TARGET_HEADS", 1),
        "rmsnorm_for_layernorm": lambda: _patched(dsa, "_layernorm", rms),
        "no_head_factor": lambda: _patched(dsa, "indexer", unscaled),
        "no_rotary_on_key": lambda: _patched(
            dsa, "_turn_first", lambda x, table: x if x.shape[2] == 1 else turn(x, table)),
        "group_best_one": lambda: _patched(
            moe, "_within_groups", lambda decide, cfg: within(
                decide, dataclasses.replace(cfg, topk_method="group_limited_greedy"))),
        "router_three_passes": lambda: _patched(
            moe, "ROUTER_PRECISION", jax.lax.Precision.HIGH),
        "bf16_router": lambda: _patched(
            moe, "ROUTER_PRECISION", jax.lax.Precision.DEFAULT),
    }


def fault(name, pc):
    """A context in which the program has the fault ``name`` (a key of
    :func:`_faults`); compiled functions made outside it do not."""
    return _faults(pc)[name]()


FAULTS = ("p_bf16", "i_bf16", "fp8_indexer", "no_relu", "target_one_head", "rmsnorm_for_layernorm",
          "no_head_factor", "no_rotary_on_key", "group_best_one")
CONTROLS = ("router_three_passes", "bf16_router")


def reading(job, adapter, cfg, sample, seq, held, ref, check, free=False, router_only=False):
    """``frozen_check``'s three parts (and, where asked, the free run that
    judges nothing) beside the frozen tree ``held``; ``router_only``: part C
    alone, for a variant that changes the router's product and nothing else."""
    out = {"router": job.router_precision(
        job.router_answers(adapter, cfg, held, ref["router_in"]), ref, check["router"])}
    if not router_only:
        replayed = job.system_answers(adapter, cfg, sample, seq, held, routing=ref["routing"])
        out["decisions"] = job.decisions(replayed["routing"], ref, check["routing"])
        out["arithmetic"] = job.arithmetic(replayed, ref, check["tolerances"])
        out["layer_losses"] = [float(x) for x in replayed["layer_losses"]]
    out["ok"] = all(part["ok"] for part in out.values() if isinstance(part, dict))
    if free:
        got = job.system_answers(adapter, cfg, sample, seq, held)
        out["free"] = {**job.arithmetic(got, ref, check["tolerances"]),
                       "decisions": job.decisions(got["routing"], ref, check["routing"])}
    return out


def loads(adapter, cfg, seeds=()):
    """For each of ``seeds`` (none: eight of this script's own), weights and
    tokens as ``jobs/bare_frozen.py`` draws them from a run's ``--seed``:
    each expert layer's pairs that reach the held experts over the even
    share, every layer's KL and the counters a trainer logs."""
    import jax
    import numpy as np

    from torchft_tpu.models.deepseek import deepseek_hidden

    pc = dataclasses.replace(adapter.config(cfg), share_room=16.0)  # every pair counted
    B, S = cfg["recipe"]["batch_size"], cfg["recipe"]["seq_len"]
    even = B * S * pc.top_k * pc.n_held / pc.num_experts
    init_ = adapter.program()[0]

    @jax.jit
    def counters(params, held, tokens):  # the layers' own stats, before a trainer's means
        stats = deepseek_hidden({**params, **held}, tokens, pc)[1]
        return {k: stats[k] for k in ("held_pairs", "overflow", "dsa_kl", "dsa_topk_mass")}

    for seed in seeds or [1000 * i + 7 for i in range(8)]:
        params = jax.jit(lambda s: init_(jax.random.PRNGKey(s), pc))(seed % 2**31)
        held = adapter.held(seed % 2**31, pc)
        tokens = jax.random.randint(jax.random.PRNGKey((seed + 1) % 2**31), (B, S), 0,
                                    pc.vocab_size)
        got = jax.device_get(counters(params, held, tokens))
        del params, held
        print(json.dumps({"loads_seed": seed, "held_over_even": [
            round(float(x), 4) for x in np.asarray(got["held_pairs"]) / even],
            "kl_layers": [round(float(x), 5) for x in got["dsa_kl"]],
            "dsa_topk_mass": [round(float(x), 5) for x in got["dsa_topk_mass"]],
            "frozen_param_share": 1 - pc.num_trainable() / pc.num_params(),
            "overflow_pairs": float(np.sum(got["overflow"]))}), flush=True)


def tiny_config(cfg):
    cfg = {**cfg, **TINY, "name": "tiny-dsv32"}
    cfg["rope_scaling"] = {**cfg["rope_scaling"], "original_max_position_embeddings": 32}
    cfg["deployment"] = {**cfg["deployment"], "experts_held": [4, 4], "router_outputs": 16,
                         "share_room": 4.0}
    cfg["recipe"] = {**cfg["recipe"], "seq_len": 80, "param_dtype": "float32"}
    return cfg


def main(argv):
    on_cpu = argv[:1] == ["cpu"]
    bench = manifest.load(ROOT)
    cell = manifest.Cell(ROOT, bench, CELL if on_cpu or not argv else argv[0])
    job, adapter = cell.job(), cell.adapter()
    cfg = tiny_config(cell.config) if on_cpu else cell.config
    seq, check = cfg["recipe"]["seq_len"], F32 if on_cpu else cell.traffic["check"]
    sample = job.check_sample_of(cell, adapter)
    if on_cpu:
        sample = {**sample, "sequences": 2, "positions": 8}
    elif argv[1:2] != ["loads"]:
        # a child computes the reference's answers before this process takes the chip
        ref = job._reference_answers(cell, adapter, sample,
                                     os.path.join(ROOT, ".chipbench_cache"))

    import jax

    if not on_cpu and jax.devices()[0].platform != "tpu":
        return 2
    if argv[1:2] == ["loads"]:
        loads(adapter, cfg, [int(x) for x in argv[2:]])
        return 0
    pc = adapter.config(cfg)
    held = adapter.held(sample["seed"], pc)
    if on_cpu:
        adapter.reference.QUERY_BLOCK = 32
        tokens, positions = adapter.reference.check_sample(cfg, sample, seq)
        params = jax.device_get({
            **adapter.program()[0](jax.random.PRNGKey(sample["seed"]), pc), **held})
        ref = adapter.reference.answers(params, tokens, cfg, positions, sample)

    def show(name, **kw):
        jax.clear_caches()
        got = reading(job, adapter, cfg, sample, seq, held, ref, check, **kw)
        print(json.dumps({"variant": name, **got}), flush=True)

    print(json.dumps({"reference": {"loss": float(ref["loss"]), "layer_losses": [
        float(x) for x in ref["layer_losses"]], "grad_norm": float(ref["grad_norm"])}}), flush=True)
    show("program", free=True)
    for name in argv[1:] or FAULTS + CONTROLS:
        with fault(name, pc):
            show(name, router_only=name in CONTROLS)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
