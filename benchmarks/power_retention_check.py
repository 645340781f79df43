"""The power-retention kernels alone on the chip, at the shape of
``brumby-14b-base.bare-retention`` (read from the cell's configuration: one
sequence of 16,384, 40 query heads over 8 key/value heads of 128, bf16 in and
out, the decays spread as the model's initialisation spreads them), against
the recurrence one position after another in float32.

One JSON line: the milliseconds of the plain forward call
(``power_retention_fwd`` as a step without a gradient runs it), of the
forward call a gradient follows (the VJP's forward rule, which also writes
what the backward kernel loads: what a training step runs, twice a layer
under remat) and of the forward-and-backward call (``y``'s cotangent in,
all four cotangents out), each kernel's share of its roofline
(``chipbench/adapters/brumby.py::retention_cost``: the recurrent form's
required products at the bf16 peak), and the distance of ``y`` and of each
gradient from the recurrence (the norm of the difference over the
recurrence's norm).

    chiprun -- python3 benchmarks/power_retention_check.py [seed]

Exits 2 without a TPU.
"""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from chipbench import flops, manifest  # noqa: E402
from torchft_tpu.models import brumby  # noqa: E402
from torchft_tpu.ops import power_retention as R  # noqa: E402

CELL = "brumby-14b-base.bare-retention"
SPAN = 128  # positions the recurrence's gradient rematerialises at a time
_F32 = jnp.float32


def inputs(cfg: dict, seed: int):
    B, T = cfg["recipe"]["batch_size"], cfg["recipe"]["seq_len"]
    hq, h, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (B, T, hq, d)).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, T, h, d)).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, T, h, d)).astype(jnp.bfloat16)
    g = jax.nn.log_sigmoid(jax.random.normal(ks[3], (B, T, h)) + brumby.gate_bias(h))
    w = jax.random.normal(ks[4], (B, T, hq, d)).astype(jnp.bfloat16)
    return (q, k, v, g), w


def recurrence(q, k, v, g):
    """``power_retention_reference``'s recurrence a key/value head at a time
    and in spans of :data:`SPAN` positions, each rematerialised: the gradient
    of 16,384 steps keeps a head's state (4.2 MB) at every span's start and
    inside one span, where the plain scan would keep every step's states of
    every head (34 MB a step)."""
    B, T, hq, d = q.shape
    h, dv = k.shape[2], v.shape[3]
    rep, scale = hq // h, d ** -0.5
    ia, ib = jnp.triu_indices(d)
    weight = jnp.where(ia == ib, 1.0, R.CROSS).astype(_F32)

    def phi(u):
        return u[..., ia] * u[..., ib] * weight

    def step(carry, inp):
        S, z = carry
        q_t, k_t, v_t, g_t = inp  # [rep, d], [d], [dv], []
        pk, decay = phi(k_t), jnp.exp(g_t)
        S, z = decay * S + pk[:, None] * v_t[None, :], decay * z + pk
        pq = phi(scale * q_t)
        return (S, z), (pq @ S) / ((pq @ z)[:, None] + R.EPS)

    @jax.checkpoint
    def span(carry, inp):
        return jax.lax.scan(step, carry, inp)

    @jax.checkpoint
    def head(args):
        init = (jnp.zeros((ia.shape[0], dv), _F32), jnp.zeros((ia.shape[0],), _F32))
        _, y = jax.lax.scan(span, init, tuple(
            m.reshape(T // SPAN, SPAN, *m.shape[1:]) for m in args))
        return y.reshape(T, rep, dv)

    def heads(m, n):  # [B, T, h * n, ...] -> [B * h, T, n, ...] float32
        m = m.astype(_F32).reshape(B, T, h, n, *m.shape[3:])
        return jnp.moveaxis(m, 2, 1).reshape(B * h, T, n, *m.shape[4:])

    with jax.default_matmul_precision("highest"):
        y = jax.lax.map(head, (heads(q, rep), heads(k, 1)[:, :, 0], heads(v, 1)[:, :, 0],
                               heads(g[..., None], 1)[:, :, 0, 0]))
    return jnp.moveaxis(y.reshape(B, h, T, rep, dv), 1, 2).reshape(B, T, hq, dv)


def timed(f, *args, n: int = 5) -> float:
    jax.block_until_ready(f(*args))
    t0 = time.monotonic()
    for _ in range(n):
        out = f(*args)
    jax.block_until_ready(out)
    return (time.monotonic() - t0) / n


def rel(a, b) -> float:
    a, b = a.astype(_F32), b.astype(_F32)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def main(argv):
    if jax.devices()[0].platform != "tpu":
        return 2
    cell = manifest.Cell(ROOT, manifest.load(ROOT), CELL)
    cfg = cell.config
    args, w = inputs(cfg, int(argv[0]) if argv else 0)

    def pulled(f):
        def run(*a):
            y, pull = jax.vjp(f, *a)
            return (y, *pull(w.astype(y.dtype)))
        return jax.jit(run)

    fwd = jax.jit(R.power_retention)
    # the forward rule's call alone: a kernel's outputs cannot be pruned
    rule = jax.jit(lambda *a: jax.vjp(R.power_retention, *a)[0])
    both = pulled(R.power_retention)
    fwd_s, rule_s, both_s = timed(fwd, *args), timed(rule, *args), timed(both, *args)
    got, want = both(*args), pulled(recurrence)(*args)
    recipe = cfg["recipe"]
    kind = jax.devices()[0].device_kind
    floor = {p: flops.roofline_floor_s(
        cell.adapter().retention_cost(cfg, recipe["batch_size"], recipe["seq_len"], p), kind)[0]
        for p in ("fwd", "bwd")}
    print(json.dumps({
        "shape": [recipe["batch_size"], recipe["seq_len"], cfg["num_attention_heads"],
                  cfg["num_key_value_heads"], cfg["head_dim"]],
        "chunk": R.CHUNK, "block": R.BLOCK, "device": kind,
        "fwd_ms": 1e3 * fwd_s, "fwd_rule_ms": 1e3 * rule_s, "fwd_bwd_ms": 1e3 * both_s,
        "bwd_ms": 1e3 * (both_s - rule_s),
        "fwd_roofline_pct": 100 * floor["fwd"] / fwd_s,
        "bwd_roofline_pct": 100 * floor["bwd"] / (both_s - rule_s),
        **{f"{n}_rel": rel(a, b) for n, a, b in zip(("y", "dq", "dk", "dv", "dg"), got, want)},
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
