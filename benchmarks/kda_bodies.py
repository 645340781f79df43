"""Seconds of one call of ``ops/kda.py``'s two bodies on the chip, forward and
forward with backward, on seeded inputs at a cell's shape: what the general body (every
``g <= 0``, ``beta`` to 2) costs beside the bounded one (``g`` in [-5, 0],
``beta`` to 1), which only ``models/ling.py``'s form of the decay may take.
Run on the chip:

    chiprun -- python3 benchmarks/kda_bodies.py [--heads 32 --seq 32768]

Prints one JSON line a body: the median of ``--repeats`` timed calls after a
warm one (the host's clock round one jitted call, the layout turns of ``kda``
inside it: the kernels alone read less in a step's device trace), the
outputs' relative distance from the bounded body's, and the device. Also
times the bounded body with the general body's doubling inverse in place of
its own product form (``bounded_doubling``): what the two inverses cost
apart from the exponents. Exits 2 without a TPU: off the chip the kernels run
interpreted and a time read there is no device's.
"""

import argparse
import contextlib
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--seq", type=int, default=32768)
    ap.add_argument("--repeats", type=int, default=5)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        sys.exit(2)

    from torchft_tpu.ops import kda as kda_ops
    from torchft_tpu.ops.kda import kda

    H, T, d = args.heads, args.seq, 128
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    unit = lambda m: m / jnp.linalg.norm(m, axis=-1, keepdims=True)  # noqa: E731
    q = (unit(jax.random.normal(ks[0], (1, T, H, d))) * d ** -0.5).astype(jnp.bfloat16)
    k = unit(jax.random.normal(ks[1], (1, T, H, d))).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, T, H, d)).astype(jnp.bfloat16)
    g = -5 * jax.nn.sigmoid(2 * jax.random.normal(ks[3], (1, T, H, d)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (1, T, H)))
    device = jax.devices()[0]
    outs = {}
    bounded = {"decay_floor": -5.0, "beta_max": 1.0}

    @contextlib.contextmanager
    def doubling():  # the bounded body's exponents over the general body's inverse
        own, kda_ops._inverse_bounded = kda_ops._inverse_bounded, kda_ops._inverse
        try:
            yield
        finally:
            kda_ops._inverse_bounded = own

    def timed(body, kw):
        fwd = jax.jit(lambda *a: kda(*a, **kw))
        both = jax.jit(jax.grad(lambda *a: jnp.sum(kda(*a, **kw).astype(jnp.float32)),
                                argnums=range(5)))
        line = {"body": body, "heads": H, "seq": T, "device": device.device_kind}
        for name, f in (("forward_s", fwd), ("forward_backward_s", both)):
            jax.block_until_ready(f(q, k, v, g, beta))
            times = []
            for _ in range(args.repeats):
                t0 = time.monotonic()
                jax.block_until_ready(f(q, k, v, g, beta))
                times.append(time.monotonic() - t0)
            line[name] = statistics.median(times)
        outs[body] = fwd(q, k, v, g, beta).astype(jnp.float32)
        print(json.dumps(line), flush=True)

    for body, kw, within in (("bounded", bounded, contextlib.nullcontext),
                             ("bounded_doubling", bounded, doubling),
                             ("general", {}, contextlib.nullcontext)):
        jax.clear_caches()
        with within():
            timed(body, kw)
    apart = lambda a: float(  # noqa: E731
        jnp.linalg.norm(outs[a] - outs["bounded"]) / jnp.linalg.norm(outs["bounded"]))
    print(json.dumps({"apart_from_bounded_rel": {a: apart(a) for a in outs if a != "bounded"}}))


if __name__ == "__main__":
    main()
