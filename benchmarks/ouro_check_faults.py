"""What ``ouro-2.6b.bare-loop-4k``'s check reads on the chip, for the program
as it is, for the precision below the one the configuration states, and for
the six faults it has to refuse:

(a) ``three_passes``: the stack run ``total_ut_steps - 1`` times;
(b) ``no_norm_between``: a pass handed the last pass's state as it left the
    stack (the exits still read it normalised);
(c) ``last_exit_not_remainder``: ``p_T = lambda_T prod (1 - lambda_j)`` like
    the exits before it, the mass left over lost;
(d) ``no_entropy``: the loss without ``- beta H(p)``;
(e) ``no_post_norms``: a dense layer, each branch joining the residual stream
    as it comes (``g2``, ``g4`` unused);
(f) ``one_pass_gradient``: the shared stack's gradient from the last pass
    alone, the passes before it reading the weights as constants;

``fp8_between`` (what a pass hands on, to its exit and to the next pass,
rounded to three mantissa bits: the nearest precision below bf16, at the four
places a step has such a state) has to come out not correct too, and with it
``fp8_residual`` (every layer application's output so rounded: 64 places);
``bf16_gate`` (the gate's weights and its logits in bfloat16, for the float32
the configuration states) is reported with what it reads.

The check is the cell's own (``chipbench/jobs/bare.py``: ``system_answers``
and ``compare`` against ``reference_ouro.py``'s answers on the fixed sample,
at the published widths, the cut's depth, one sequence of 4,096); the faults
are put into ``torchft_tpu/models/ouro.py`` from here, the program has no
switch for them, and the CPU tests put the same ones in at a small size.
More gradient leaves are sampled than the cell samples, so that the readings
say which leaf sees a fault best.

    chiprun -- python3 benchmarks/ouro_check_faults.py [workload [variant ...]]

One JSON line per variant; exits 2 without a TPU.
"""

import contextlib
import dataclasses
import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import manifest  # noqa: E402

_spec = importlib.util.spec_from_file_location(
    "lfm2_check_faults", os.path.join(ROOT, "benchmarks", "lfm2_check_faults.py"))
_lfm2 = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_lfm2)
_patched, _fp8_like = _lfm2._patched, _lfm2._fp8_like

MORE_LEAVES = ["exit_gate.b", "layers.ffn_post_norm", "layers.w_gate", "layers.attn_norm"]
FAULTS = ("three_passes", "no_norm_between", "last_exit_not_remainder", "no_entropy",
          "no_post_norms", "one_pass_gradient")
BELOW = ("fp8_between", "fp8_residual")
REPORTED = ("bf16_gate",)


def _exits(carry_normed=True, last_pass_gradient_only=False):
    """``ouro.ouro_exits`` from the module's own pieces, the passes a Python
    loop, with a fault: what a pass starts from, or which passes' use of the
    weights the gradient sees."""
    import jax
    import jax.numpy as jnp

    from torchft_tpu.models import ouro

    def exits(params, tokens, cfg, attention_fn=None, remat="full"):
        body = ouro.remat_wrap(ouro.make_llama_layer_body(cfg, attention_fn), remat)
        h, out = params["embed"][tokens], []
        for t in range(cfg.total_ut_steps):
            layers = params["layers"]
            if last_pass_gradient_only and t < cfg.total_ut_steps - 1:
                layers = jax.lax.stop_gradient(layers)
            u, _ = jax.lax.scan(body, h, layers)
            out.append(ouro._between(params["final_norm"], u, cfg))
            h = out[-1] if carry_normed else u
        return jnp.stack(out)

    return exits


def _faults():
    import jax
    import jax.numpy as jnp

    from torchft_tpu.models import ouro

    exits, loss, between = ouro.ouro_exits, ouro.exit_loss, ouro._between
    log_probs = ouro.exit_log_probs
    body = ouro.make_llama_layer_body

    def dense_body(cfg, attention_fn=None):
        layer = body(cfg, attention_fn)
        return lambda h, w: layer(h, {k: v for k, v in w.items()
                                      if not k.endswith("post_norm")})

    def fp8_body(cfg, attention_fn=None):
        layer = body(cfg, attention_fn)
        return lambda h, w: (_fp8_like(layer(h, w)[0]), None)

    def like_the_others(z):
        stop, go = jax.nn.log_sigmoid(z), jax.nn.log_sigmoid(-z)
        return stop + jnp.cumsum(go, axis=0) - go

    def bf16_gate(hs, lm_head, gate, *args, **kw):
        rounded = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16).astype(jnp.float32), gate)
        with _patched(ouro, "exit_log_probs", lambda z: log_probs(
                z.astype(jnp.bfloat16)).astype(jnp.float32)):
            return loss(hs, lm_head, rounded, *args, **kw)

    return {
        "three_passes": lambda: _patched(
            ouro, "ouro_exits", lambda p, tok, cfg, **kw: exits(
                p, tok, dataclasses.replace(cfg, total_ut_steps=cfg.total_ut_steps - 1), **kw)),
        "no_norm_between": lambda: _patched(ouro, "ouro_exits", _exits(carry_normed=False)),
        "last_exit_not_remainder": lambda: _patched(ouro, "exit_log_probs", like_the_others),
        "no_entropy": lambda: _patched(
            ouro, "exit_loss", lambda hs, lm_head, gate, targets, beta, chunk=0: loss(
                hs, lm_head, gate, targets, 0.0, chunk)),
        "no_post_norms": lambda: _patched(ouro, "make_llama_layer_body", dense_body),
        "one_pass_gradient": lambda: _patched(
            ouro, "ouro_exits", _exits(last_pass_gradient_only=True)),
        "fp8_between": lambda: _patched(
            ouro, "_between", lambda g, u, cfg: _fp8_like(between(g, u, cfg))),
        "fp8_residual": lambda: _patched(ouro, "make_llama_layer_body", fp8_body),
        "bf16_gate": lambda: _patched(ouro, "exit_loss", bf16_gate),
    }


def fault(name):
    """A context in which the program has the fault ``name`` (a key of
    :func:`_faults`); compiled functions made outside it do not."""
    return _faults()[name]()


def main(argv):
    bench = manifest.load(ROOT)
    cell = manifest.Cell(ROOT, bench, argv[0] if argv else "ouro-2.6b.bare-loop-4k")
    bare, adapter = cell.job(), cell.adapter()
    cfg, seq = cell.config, cell.config["recipe"]["seq_len"]
    sample = bare.check_sample_of(cell, adapter)
    sample = {**sample, "grad_leaves": sample["grad_leaves"] + MORE_LEAVES}
    # a child computes the reference's answers before this process takes the chip
    ref = bare._reference_answers(cell, adapter, sample,
                                  os.path.join(ROOT, ".chipbench_cache"))

    import jax

    if jax.devices()[0].platform != "tpu":
        return 2
    tol = cell.traffic["check"]["tolerances"]

    def reading(name, context=contextlib.nullcontext()):
        jax.clear_caches()
        with context:
            got = bare.compare(bare.system_answers(adapter, cfg, sample, seq), ref, tol)
        print(json.dumps({"variant": name, **got}), flush=True)

    reading("program")
    for name in argv[1:] or BELOW + FAULTS + REPORTED:
        reading(name, fault(name))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
