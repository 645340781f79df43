"""Job kind ``bare``: the configuration's recipe as one fused, donated optax
step on one chip, no Manager — the ceiling the managed cells are read
against. The loop is ``bench.py``'s ``timed_train_step`` (jit with donated
params and optimizer state, warm-up forced to a host scalar, steps chained
through the donated state, a value fetch at the end of the window), copied
because the program may change and the yardstick may not; the optimizer is
the trainer's (adamw, weight decay 0.1), weights and tokens come from
``--seed``. Before the window it compares the program's loss path (splash,
remat full, bf16) with the plain reference on a fixed seeded sample; the
reference's answers are computed once per checkout by a child process that
holds the chip before this one touches JAX. What is one architecture's own
(the program's init, loss and forward, its config object, its plain
reference, the gradient leaves the check samples) comes from the
configuration's adapter, chipbench/adapters/<name>.py: this file names no
model.
"""

import hashlib
import json
import os
import subprocess
import sys
import time

import numpy as np

from chipbench.worker import REPO

SEEDS = 2**31  # --seed may be larger than an int32 holds; a jitted argument is one


def check_sample_of(cell, adapter) -> dict:
    """The traffic file's check sample, with the adapter's gradient leaves
    where its tree has other names than the traffic file's."""
    sample = cell.traffic["check"]["sample"]
    if adapter.GRAD_LEAVES:
        sample = {**sample, "grad_leaves": adapter.GRAD_LEAVES}
    return sample


def _reference_answers(cell, adapter, sample: dict, cache_dir: str) -> dict:
    """The reference's answers for the check sample, from the checkout's
    cache or from a child that runs before this process takes the chip."""
    script = adapter.reference.__file__
    digest = hashlib.sha256(json.dumps(sample, sort_keys=True).encode())
    for p in (cell.config_path, script):
        with open(p, "rb") as f:
            digest.update(f.read())
    key = digest.hexdigest()[:16]
    out = os.path.join(cache_dir, f"reference_{cell.workload['config']}_{key}.npz")
    if not os.path.exists(out):
        os.makedirs(cache_dir, exist_ok=True)
        spec = os.path.join(cache_dir, f"sample_{key}.json")
        with open(spec, "w") as f:
            json.dump(sample, f)
        tmp = out[:-4] + ".tmp.npz"
        subprocess.run([sys.executable, script, cell.config_path, spec, tmp],
                       check=True, cwd=REPO)
        os.replace(tmp, out)
    got = np.load(out)
    return {k: got[k] for k in got.files}


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def compare(system: dict, ref: dict, tol: dict) -> dict:
    """The comparison that decides ``correct`` for the model path.

    Tolerances (chipbench/traffic/bare.json, ``check.tolerances``), and why:
    the program computes in bf16 with f32 accumulation from the same bf16
    weights the reference upcasts. One bf16 rounding is 2^-9 = 2e-3
    relative; a few tens of them in sequence through the cut depth give a
    relative Frobenius error of the logits of 1e-2, and the backward pass
    carries the same roundings into every gradient leaf. fp8 operands (2^-4
    a rounding) or bf16 accumulation over 4096-14336 terms land above 5e-2
    in logits or leaves, so 2-3e-2 fails them. Loss is a mean over 2048
    tokens of values near ln(vocab), and the global gradient norm a sum of
    squares over 1e9 elements: rounding averages out of both, so they are
    held to ten times what the chip showed (PERF.md section 6), which
    catches a bias, and the sampled leaves catch what averages out.
    """
    out = {"logits_rel": _rel(system["logits"], ref["logits"]),
           "loss_abs": abs(float(system["loss"]) - float(ref["loss"])),
           "grad_norm_rel": abs(float(system["grad_norm"]) / float(ref["grad_norm"]) - 1.0)}
    limits = {k: tol[k] for k in out}
    for k in ref:
        if k.startswith("grad."):
            out["grad_rel." + k[5:]] = _rel(system[k], ref[k])
            limits["grad_rel." + k[5:]] = tol["grad_leaf_rel"]
    out["ok"] = all(np.isfinite(out[k]) and out[k] <= limits[k] for k in limits)
    return out


def system_answers(adapter, cfg: dict, sample: dict, seq: int) -> dict:
    """The program's side of the check: the adapter's loss and forward with
    the default attention dispatch and remat full, in the served dtype."""
    import jax

    reference = adapter.reference
    init_, loss_, forward_ = adapter.program()
    pc = adapter.config(cfg)
    tokens, positions = reference.check_sample(cfg, sample, seq)
    params = jax.jit(lambda: init_(jax.random.PRNGKey(sample["seed"]), pc))()

    def both(p):  # one program: XLA shares the forward pass between the two
        return (loss_(p, tokens, tokens, pc, remat="full"),
                forward_(p, tokens, pc, remat="full")[:, positions])

    @jax.jit
    def run_(p):
        (val, logits), grads = jax.value_and_grad(both, has_aux=True)(p)
        return val, logits, reference.grad_answers(grads, sample)

    val, logits, grads = run_(params)
    return {"logits": np.asarray(logits, np.float32), "loss": float(val),
            **{k: np.asarray(v) for k, v in grads.items()}}


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
    verdict = compare(system_answers(adapter, cfg, sample, S), ref,
                      check["tolerances"])
    marks["check_s"] = time.monotonic() - t_start

    init_, loss_, _ = adapter.program()
    pc = adapter.config(cfg)
    tx = optax.adamw(recipe["lr"], weight_decay=recipe["weight_decay"])

    @jax.jit
    def init(seed):  # an argument, not a constant: one cached program for every seed
        params = init_(jax.random.PRNGKey(seed), pc)
        return params, tx.init(params)

    def step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(loss_)(
            params, tokens, tokens, pc, remat=recipe["remat"])
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    jstep = jax.jit(step, donate_argnums=(0, 1))
    params, opt_state = init(seed % SEEDS)
    tokens = jax.random.randint(jax.random.PRNGKey((seed + 1) % SEEDS), (B, S), 0,
                                cfg["vocab_size"])
    jax.block_until_ready(params)
    marks["init_s"] = time.monotonic() - t_start
    losses = []
    for _ in range(tr["warmup_steps"]):  # compiles (or loads), then one warm
        t0 = time.monotonic()
        params, opt_state, loss = jstep(params, opt_state, tokens)
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
        params, opt_state, loss = jstep(params, opt_state, tokens)
    losses.append(float(loss))  # the chain ends here: value fetch = barrier
    wall = time.monotonic() - t0
    obs = {"steps_in_window": n}
    if trace:
        jax.profiler.stop_trace()
        t = xplane.read(xplane.find(os.path.join(out_dir, "trace")))
        obs["trace"] = xplane.merge([xplane.reduce(t, [
            (a[0], a[1], a[2]) for a in t["annotations"]])])
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
