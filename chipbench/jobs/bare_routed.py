"""Job kind ``bare_routed``: ``bare`` for a model that routes tokens to
experts. The timed loop is jobs/bare.py's, word for word (a test holds the two
``run`` functions equal but for the one statement that makes the check); the
timed step routes freely. The check is another, because ``bare``'s cannot
decide ``correct`` for a routed model and no tolerance can mend that: bf16
rounding of a router's input moves its logits by some 1e-3 while a token's
k-th and (k+1)-th expert lie a few 1e-2 apart, so a few percent of tokens
pick one other expert than the float32 reference, each such token's expert
output differs by tens of percent, and a tolerance wide enough for that lets
an fp8 matmul pass. So the decisions and the arithmetic are compared apart:

(A) **decisions.** The program runs the sample *replaying the reference's
    routing* and returns, layer by layer, what it would have chosen from its
    own input. Every (layer, token) pair whose set of experts differs from
    the reference's must be a near-tie in the reference, ``(p_k - p_k+1) /
    p_k <= max_margin``, and such pairs at most ``max_share`` of all
    (``check.routing`` of the traffic file, with the readings they were set
    from).
(B) **arithmetic.** Under that replay both sides used the same experts, and
    jobs/bare.py's ``compare`` holds logits, loss, gradient norm and the
    sampled gradient leaves to ``check.tolerances``, which are ``bare``'s.

(C) **the router alone.** A cannot see the router's own precision: the
    router's input already carries the bf16 arithmetic of everything before
    it, ten times what a router product in one bf16 pass adds. So the
    reference also hands out what each layer's router was given
    (``router_in``, float32), the program's block is put before those very
    numbers (the adapter's ``router_alone``), and its k-th and (k+1)-th
    probabilities must equal the reference's within ``check.router``'s
    ``max_prob_rel`` (summation order in float32 passes, a product in a
    lower precision does not), the chosen experts identical but where the
    reference's margin is under twice that limit: a tie to that precision.

``correct`` = A and B and C. The same comparison with the program routing
freely is recorded beside them (``free``) and judges nothing. What is the
model's own comes from the configuration's adapter: its ``program()``'s loss
and forward take ``routing=`` (and the loss ``with_stats=True``), its
reference's answers carry ``routing``, ``p_kth``, ``p_next`` and
``router_in``; they are cached with the answers, in the same file under the
same key. This file names no model.

A traced run also hands out ``scopes``: the compiled step's ``op_name`` (the
program's ``jax.named_scope`` path) of every traced instruction, for the
``device_scope`` reducer: a device trace names instructions only.
"""

import os
import re
import time

import numpy as np

from chipbench import manifest
from chipbench.worker import REPO

_bare = manifest.load_module(REPO, "jobs", "bare")
SEEDS, check_sample_of, compare = _bare.SEEDS, _bare.check_sample_of, _bare.compare
_reference_answers = _bare._reference_answers


def system_answers(adapter, cfg: dict, sample: dict, seq: int, routing=None) -> dict:
    """The program's side of the check, as jobs/bare.py's, with the routing
    to replay (None: its own) and, beside the answers, the routing it would
    have chosen freely (``routing`` [L, T, k])."""
    import jax

    reference = adapter.reference
    init_, loss_, forward_ = adapter.program()
    pc = adapter.config(cfg)
    tokens, positions = reference.check_sample(cfg, sample, seq)
    params = jax.jit(lambda: init_(jax.random.PRNGKey(sample["seed"]), pc))()

    def both(p):  # one program: XLA shares the forward pass between the two
        val, stats = loss_(p, tokens, tokens, pc, remat="full", routing=routing,
                           with_stats=True)
        return val, (forward_(p, tokens, pc, remat="full", routing=routing)[:, positions],
                     stats["routing"])

    @jax.jit
    def run_(p):
        (val, (logits, free)), grads = jax.value_and_grad(both, has_aux=True)(p)
        return val, logits, free, reference.grad_answers(grads, sample)

    val, logits, free, grads = run_(params)
    return {"logits": np.asarray(logits, np.float32), "loss": float(val),
            "routing": np.asarray(free), **{k: np.asarray(v) for k, v in grads.items()}}


def _missing(ours, theirs):
    """Per (layer, token): experts of ``ours`` [L, T, k] that are not among
    ``theirs``."""
    ours, theirs = np.asarray(ours), np.asarray(theirs)
    return np.sum(~np.any(ours[..., :, None] == theirs[..., None, :], axis=-1), axis=-1)


def decisions(free, ref: dict, limits: dict) -> dict:
    """Part A. ``free`` [L, T, k]: the program's own choices under replay;
    ``ref``: the reference's ``routing``, ``p_kth``, ``p_next``."""
    missing = _missing(free, ref["routing"])
    differ = missing > 0  # [L, T]
    margin = (ref["p_kth"] - ref["p_next"]) / ref["p_kth"]
    worst = float(margin[differ].max()) if differ.any() else 0.0
    share = float(differ.mean())
    return {"differ_share": share, "differ_max_margin": worst,
            "differ_pairs": int(differ.sum()), "pairs": int(differ.size),
            "differ_by_layer": [float(x) for x in differ.mean(axis=1)],
            # pairs that differ in more than one expert: p_k against p_k+1 is
            # then not the whole of the tie; they are few
            "differ_in_two": int((missing > 1).sum()),
            "ok": bool(share <= limits["max_share"] and worst <= limits["max_margin"])}


def router_answers(adapter, cfg: dict, sample: dict, router_in) -> dict:
    """The program's routers (its own seeded weights) before the inputs the
    reference's routers were given: ``routing``, ``p_kth``, ``p_next``."""
    import jax

    pc = adapter.config(cfg)
    params = jax.jit(lambda: adapter.program()[0](jax.random.PRNGKey(sample["seed"]), pc))()
    got = jax.jit(lambda p, x: adapter.router_alone(p, pc, x))(params, router_in)
    return {k: np.asarray(v) for k, v in got.items()}


def router_precision(ours: dict, ref: dict, limits: dict) -> dict:
    """Part C. ``ours``: the program's router on the reference's router
    inputs. Where both chose the same experts the k-th and (k+1)-th
    probabilities are held to ``max_prob_rel``; where they did not, the
    reference's margin must be a tie to that precision (both may move by
    the limit, towards each other)."""
    limit = limits["max_prob_rel"]
    differ = _missing(ours["routing"], ref["routing"]) > 0  # [L, T]
    margin = (ref["p_kth"] - ref["p_next"]) / ref["p_kth"]
    same = ~differ
    rel = max(float(np.max(np.abs(ours[k] - ref[k])[same] / ref[k][same]))
              for k in ("p_kth", "p_next")) if same.any() else float("inf")
    worst = float(margin[differ].max()) if differ.any() else 0.0
    return {"prob_rel": rel, "differ_pairs": int(differ.sum()),
            "differ_max_margin": worst, "pairs": int(differ.size),
            "ok": bool(rel <= limit and worst <= 2 * limit)}


def routed_check(adapter, cfg: dict, sample: dict, seq: int, ref: dict, check: dict) -> dict:
    replayed = system_answers(adapter, cfg, sample, seq, routing=ref["routing"])
    a = decisions(replayed["routing"], ref, check["routing"])
    b = compare(replayed, ref, check["tolerances"])
    c = router_precision(router_answers(adapter, cfg, sample, ref["router_in"]),
                         ref, check["router"])
    free = system_answers(adapter, cfg, sample, seq)
    return {"ok": a["ok"] and b["ok"] and c["ok"],
            "decisions": a, "arithmetic": b, "router": c,
            "free": {**compare(free, ref, check["tolerances"]),
                     "decisions": decisions(free["routing"], ref, check["routing"])}}


def scopes_of(compiled_text: str, names) -> dict:
    """Of the traced instructions ``names``, each one's ``op_name`` in the
    compiled program's text (absent where it carries no metadata)."""
    found = {}
    for m in re.finditer(r"^\s*(?:ROOT )?%?([\w.\-]+) = .*?metadata=\{[^}]*?"
                         r"op_name=\"([^\"]*)\"", compiled_text, re.M):
        found.setdefault(m[1], m[2])
    return {n: found[n] for n in names if n in found}


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
    verdict = routed_check(adapter, cfg, sample, S, ref, check)
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
        obs["scopes"] = scopes_of(
            jstep.lower(params, opt_state, tokens).compile().as_text(), obs["trace"]["ops"])
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
