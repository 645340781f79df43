"""The alignment kernels of ``ops/dsa.py`` alone on the chip: at a length
whose ``[T, T]`` matrices fit (2,048) against the dense formula in float32
(``index_kl_reference``: value and the three gradients, as the norm of the
difference over the reference's norm), and at the shape of
``deepseek-v3.2-exp.bare-dsa-warmup-16k`` (one sequence of 16,384, 128 main
heads of 192 padded to 256, 64 indexer heads of 128, bf16) the milliseconds
of the forward pair (``dsa_kl_fwd_lse`` + ``dsa_kl_fwd``) and of forward and
backward together, for each ``BLOCK`` named on the command line (default:
the module's).

    chiprun -- python3 benchmarks/dsa_kernel_check.py [block ...]

One JSON line. Exits 2 without a TPU.
"""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from torchft_tpu.ops import dsa  # noqa: E402

_F32 = jnp.float32


def inputs(T: int, H: int = 128, D: int = 256, HI: int = 64, dI: int = 128, seed: int = 0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    bf = lambda k, shape, s=1.0: (s * jax.random.normal(k, shape)).astype(jnp.bfloat16)  # noqa: E731
    # 192 of the 256 carry values, as the mixer pads them
    live = (jnp.arange(D) < 192).astype(jnp.bfloat16)
    return (bf(ks[0], (1, T, H, D)) * live, bf(ks[1], (1, T, H, D)) * live,
            bf(ks[2], (1, T, HI, dI)), bf(ks[3], (1, T, dI)),
            jax.random.normal(ks[4], (1, T, HI), _F32) * (HI * dI) ** -0.5)


def both(fn, scale):
    return jax.jit(lambda q, k, qI, kI, w: jax.value_and_grad(
        lambda qI, kI, w: jnp.mean(fn(q, k, scale, qI, kI, w)), argnums=(0, 1, 2))(qI, kI, w))


def ms(f, *args, n=3):
    jax.block_until_ready(f(*args))
    t0 = time.monotonic()
    for _ in range(n):
        out = f(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.monotonic() - t0) / n


def main(argv):
    if jax.devices()[0].platform != "tpu":
        print("benchmarks/dsa_kernel_check.py: no TPU", file=sys.stderr)
        return 2
    # the cell's own scale (256^-0.5 x 1.87386 x (256 / 192)^0.5) over 4: on products of 192
    # unit normals that is scores a few tenths of a nat apart, as random weights give
    scale = 256 ** -0.5 * 1.87386 * (256 / 192) ** 0.5 / 4
    out = {"device": jax.devices()[0].device_kind}
    args = inputs(2048)
    (a, ga), (b, gb) = both(dsa.index_kl, scale)(*args), both(dsa.index_kl_reference, scale)(*args)
    rel = lambda x, y: float(jnp.linalg.norm((x - y).astype(_F32))  # noqa: E731
                             / jnp.linalg.norm(y.astype(_F32)))
    out["at_2048"] = {"kl": float(a), "kl_reference": float(b),
                      **{f"d{n}_rel": rel(x, y) for n, x, y in zip(("qI", "kI", "w"), ga, gb)}}
    args = inputs(16384)
    for block in [int(x) for x in argv] or [dsa.BLOCK]:
        dsa.BLOCK = block
        try:
            fwd = jax.jit(lambda *a: jnp.mean(dsa.index_kl(a[0], a[1], scale, *a[2:])))
            out[f"block_{block}"] = {"fwd_ms": ms(fwd, *args),
                                     "fwd_bwd_ms": ms(both(dsa.index_kl, scale), *args)}
        except Exception as e:  # a block the compiler refuses is a finding, not a crash
            out[f"block_{block}"] = {"error": str(e)[:300]}
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
