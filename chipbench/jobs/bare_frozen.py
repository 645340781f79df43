"""Job kind ``bare_frozen``: ``bare_routed`` for a step that trains a small
part of a model beside a FROZEN trunk (a stage of continued training: an
adapter, a new scorer, a new head). What it adds to ``bare_routed``:

- **two trees.** The adapter's ``program()`` init hands out the trainable
  leaves alone, as every adapter with state does, and ``adapter.held(seed,
  pc)`` makes the frozen tree, ONCE a phase: 8 GiB of bf16 leaves cannot be
  rebuilt from a seed inside the loss as a selection bias is. The fused
  step takes the trainable tree and its optimizer state DONATED and the held
  tree beside them, not donated and not returned: ``value_and_grad`` is over
  the trainable tree, so no cotangent, no moment and no update of a held
  leaf exists in the timed program. The timed loop is jobs/bare.py's, word
  for word but for the statements that carry the held tree (a test holds
  the two ``run`` functions to that).
- **the check's sample of the model.** Such a stage may have no logits (its
  loss need not be over a vocabulary): the adapter's loss hands out, with
  ``with_stats=True``, ``hidden`` [B, S, D], which stands where ``bare``'s
  ``compare`` reads logits, and ``layer_losses`` [layers], each layer's term
  of the loss, held to ``check.tolerances``' ``layer_loss_rel`` (the largest
  relative difference over the layers: a sum can hide two layers that err
  against each other).

The check is ``bare_routed``'s A (decisions under replay), B (arithmetic
under replay: ``compare``, and the layers' terms) and C (the router alone),
with that file's ``decisions``, ``router_precision`` and jobs/bare.py's
``compare``; only the two functions that run the program are this file's,
because they must pass the held tree. The check's held tree is dropped
before the timed one is made. This file names no model.
"""

import os
import time

import numpy as np

from chipbench import manifest
from chipbench.worker import REPO

_routed = manifest.load_module(REPO, "jobs", "bare_routed")
SEEDS, check_sample_of, compare = _routed.SEEDS, _routed.check_sample_of, _routed.compare
_reference_answers, scopes_of = _routed._reference_answers, _routed.scopes_of
decisions, router_precision = _routed.decisions, _routed.router_precision


def system_answers(adapter, cfg: dict, sample: dict, seq: int, held, routing=None) -> dict:
    """The program's side of the check, as jobs/bare_routed.py's, the frozen
    tree ``held`` beside the seeded trainable one: the gradient is over the
    trainable leaves alone."""
    import jax

    reference = adapter.reference
    init_, loss_, _ = adapter.program()
    pc = adapter.config(cfg)
    tokens, positions = reference.check_sample(cfg, sample, seq)
    params = jax.jit(lambda: init_(jax.random.PRNGKey(sample["seed"]), pc))()

    def both(p, held):
        val, stats = loss_({**p, **held}, tokens, tokens, pc, remat="full", routing=routing,
                           with_stats=True)
        return val, (stats["hidden"][:, positions], stats["routing"], stats["layer_losses"])

    @jax.jit
    def run_(p, held):
        (val, (hidden, free, terms)), grads = jax.value_and_grad(both, has_aux=True)(p, held)
        return val, hidden, free, terms, reference.grad_answers(grads, sample)

    val, hidden, free, terms, grads = run_(params, held)
    return {"logits": np.asarray(hidden, np.float32), "loss": float(val),
            "routing": np.asarray(free), "layer_losses": np.asarray(terms, np.float32),
            **{k: np.asarray(v) for k, v in grads.items()}}


def router_answers(adapter, cfg: dict, held, router_in) -> dict:
    """The program's routers (the held tree's) before the inputs the
    reference's routers were given."""
    import jax

    pc = adapter.config(cfg)
    got = jax.jit(lambda h, x: adapter.router_alone(h, pc, x))(held, router_in)
    return {k: np.asarray(v) for k, v in got.items()}


def arithmetic(system: dict, ref: dict, tol: dict) -> dict:
    """Part B: jobs/bare.py's ``compare`` (the hidden states where it reads
    logits) and each layer's term of the loss."""
    out = compare(system, ref, tol)
    out["layer_loss_rel"] = float(np.max(np.abs(
        np.asarray(system["layer_losses"], np.float64) / ref["layer_losses"] - 1.0)))
    out["ok"] = bool(out["ok"] and out["layer_loss_rel"] <= tol["layer_loss_rel"])
    return out


def frozen_check(adapter, cfg: dict, sample: dict, seq: int, ref: dict, check: dict) -> dict:
    held = adapter.held(sample["seed"], adapter.config(cfg))  # once, for all four programs
    replayed = system_answers(adapter, cfg, sample, seq, held, routing=ref["routing"])
    a = decisions(replayed["routing"], ref, check["routing"])
    b = arithmetic(replayed, ref, check["tolerances"])
    c = router_precision(router_answers(adapter, cfg, held, ref["router_in"]),
                         ref, check["router"])
    free = system_answers(adapter, cfg, sample, seq, held)
    return {"ok": a["ok"] and b["ok"] and c["ok"],
            "decisions": a, "arithmetic": b, "router": c,
            "free": {**arithmetic(free, ref, check["tolerances"]),
                     "decisions": decisions(free["routing"], ref, check["routing"])}}


def run(cell, seed: int, seconds: float, trace: bool, out_dir: str,
        cache_dir: str, t_start: float) -> dict:
    cfg, tr = cell.config, cell.traffic
    recipe = cfg["recipe"]
    B, S = recipe["batch_size"], recipe["seq_len"]
    adapter = cell.adapter()
    sample = check_sample_of(cell, adapter)
    ref = _reference_answers(cell, adapter, sample, cache_dir)  # before JAX: the child's chip

    import jax
    import jax.monitoring
    import optax

    from chipbench import xplane
    from torchft_tpu.ops import attention as attention_ops
    from torchft_tpu.utils import enable_compilation_cache

    enable_compilation_cache()
    cache = {"hits": 0, "misses": 0}

    def count(event: str, **_kw) -> None:
        for kind in cache:
            cache[kind] += event == "/jax/compilation_cache/cache_" + kind

    jax.monitoring.register_event_listener(count)
    marks = {"imports_s": time.monotonic() - t_start}
    d = jax.devices()
    marks["devices_s"] = time.monotonic() - t_start
    device = {"platform": d[0].platform, "kind": d[0].device_kind, "count": len(d)}
    if device["platform"] != "tpu":
        raise RuntimeError(f"no TPU: JAX reports {device}")
    if str(ref["platform"]) != "tpu":
        raise RuntimeError("the cached reference was not computed on a TPU")
    check = tr["check"]
    verdict = frozen_check(adapter, cfg, sample, S, ref, check)
    marks["check_s"] = time.monotonic() - t_start

    init_, loss_, _ = adapter.program()
    pc = adapter.config(cfg)
    tx = optax.adamw(recipe["lr"], weight_decay=recipe["weight_decay"])

    @jax.jit
    def init(seed):  # an argument, not a constant: one cached program for every seed
        params = init_(jax.random.PRNGKey(seed), pc)
        return params, tx.init(params)

    def step(params, opt_state, held, tokens):
        loss, grads = jax.value_and_grad(lambda p: loss_(
            {**p, **held}, tokens, tokens, pc, remat=recipe["remat"]))(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    jstep = jax.jit(step, donate_argnums=(0, 1))
    params, opt_state = init(seed % SEEDS)
    held = adapter.held(seed % SEEDS, pc)
    tokens = jax.random.randint(jax.random.PRNGKey((seed + 1) % SEEDS), (B, S), 0,
                                cfg["vocab_size"])
    jax.block_until_ready(params)
    marks["init_s"] = time.monotonic() - t_start
    losses = []
    for _ in range(tr["warmup_steps"]):  # compiles (or loads), then one warm
        t0 = time.monotonic()
        params, opt_state, loss = jstep(params, opt_state, held, tokens)
        losses.append(float(loss))
        warm_s = time.monotonic() - t0

    n = max(tr["min_steps"], int(seconds / warm_s))
    if trace:
        n = tr["trace_steps"]
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level, opts.host_tracer_level = 0, 1
        jax.profiler.start_trace(os.path.join(out_dir, "trace"),
                                 profiler_options=opts)
    setup_s = time.monotonic() - t_start
    t0 = time.monotonic()
    for _ in range(n):
        params, opt_state, loss = jstep(params, opt_state, held, tokens)
    losses.append(float(loss))  # the chain ends here: value fetch = barrier
    wall = time.monotonic() - t0
    obs = {"steps_in_window": n}
    if trace:
        jax.profiler.stop_trace()
        t = xplane.read(xplane.find(os.path.join(out_dir, "trace")))
        obs["trace"] = xplane.merge([xplane.reduce(t, [
            (a[0], a[1], a[2]) for a in t["annotations"]])])
        obs["scopes"] = scopes_of(
            jstep.lower(params, opt_state, held, tokens).compile().as_text(),
            obs["trace"]["ops"])
    peak = max(x.memory_stats()["peak_bytes_in_use"] for x in jax.local_devices())
    finite = all(np.isfinite(x) for x in losses)
    obs.update({
        "device": device, "memory_peak_bytes": peak,
        "correct": bool(verdict["ok"] and finite
                        and attention_ops.LAST_DISPATCH == recipe["attention"]),
        "attempted": n, "failed": 0,
        "e2e": {tr["metric"]: B * S * n / wall, "peak_hbm_gib": peak / 2**30,
                "setup_s": setup_s},
        "phases": {}, "steps": {}, "procs": [],
        "notes": {"check": verdict, "warm_step_s": warm_s, "wall_s": wall,
                  "losses": losses, "attention": attention_ops.LAST_DISPATCH,
                  "marks": marks, "cache": cache},
    })
    return obs
