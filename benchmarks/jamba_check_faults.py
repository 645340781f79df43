"""What ``jamba2-3b.bare-scan``'s check reads on the chip, for the program
as it is and for the two faults it has to refuse: the scan's decay and state
rounded to bfloat16 after every step, and the carry across the kernel's
chunks zeroed. The check is the cell's own (``chipbench/jobs/bare.py``:
``system_answers`` and ``compare`` against ``reference_jamba.py``'s answers
on the fixed sample, at the published widths, depth 14, one sequence of
8,192); the faults are put into ``torchft_tpu/ops/selective_scan.py`` from
here, the program has no switch for them. More gradient leaves are sampled
than the cell samples, so that the readings say which leaf sees a fault
best.

    chiprun -- python3 benchmarks/jamba_check_faults.py [workload]

One JSON line per variant; exits 2 without a TPU.
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import manifest  # noqa: E402

LEAVES = ["embed", "layers.00_mamba.x_proj", "layers.00_mamba.w_down",
          "layers.00_mamba.A_log", "layers.00_mamba.D", "layers.00_mamba.dt_bias",
          "layers.00_mamba.dt_proj", "layers.00_mamba.in_proj",
          "layers.02_mamba.A_log", "layers.02_mamba.x_proj", "layers.01_attn.wq"]


def no_carry(ss):
    """``ss``'s kernels with every chunk folded into the batch: each starts
    from a zero state, and no adjoint state crosses a chunk either."""
    forward, backward = ss._forward, ss._backward

    def fold(m, chunk):  # [B,T,d] -> [B*T/chunk, chunk, d]
        return m.reshape(-1, chunk, m.shape[-1])

    def fold_t(m, chunk):  # [B,N,T] -> [B*T/chunk, N, chunk]
        b, n, t = m.shape
        return m.reshape(b, n, t // chunk, chunk).swapaxes(1, 2).reshape(-1, n, chunk)

    def unfold_t(m, b):  # the inverse
        k, n, chunk = m.shape
        return m.reshape(b, k // b, n, chunk).swapaxes(1, 2).reshape(b, n, -1)

    def fwd(x, dt, a_t, b_t, c_t, chunk, block):
        chunk = ss._sizes(x.shape[1], x.shape[2], chunk, block)[0]
        y, hs = forward(fold(x, chunk), fold(dt, chunk), a_t, fold_t(b_t, chunk),
                        fold_t(c_t, chunk), chunk, block)
        return y.reshape(x.shape), hs.reshape(x.shape[0], -1, *hs.shape[2:])

    def bwd(x, dt, a_t, b_t, c_t, hs, dy, chunk, block):
        chunk = ss._sizes(x.shape[1], x.shape[2], chunk, block)[0]
        dx, ddt, da, db, dc = backward(
            fold(x, chunk), fold(dt, chunk), a_t, fold_t(b_t, chunk),
            fold_t(c_t, chunk), hs.reshape(-1, 1, *hs.shape[2:]), fold(dy, chunk),
            chunk, block)
        return (dx.reshape(x.shape), ddt.reshape(x.shape), da,
                unfold_t(db, x.shape[0]), unfold_t(dc, x.shape[0]))

    ss._forward, ss._backward = fwd, bwd

    def undo():
        ss._forward, ss._backward = forward, backward
    return undo


def main(argv):
    bench = manifest.load(ROOT)
    cell = manifest.Cell(ROOT, bench, argv[0] if argv else "jamba2-3b.bare-scan")
    bare, adapter = cell.job(), cell.adapter()
    cfg, seq = cell.config, cell.config["recipe"]["seq_len"]
    sample = {**bare.check_sample_of(cell, adapter), "grad_leaves": LEAVES}
    # a child computes the reference's answers before this process takes the chip
    ref = bare._reference_answers(cell, adapter, sample,
                                  os.path.join(ROOT, ".chipbench_cache"))

    import jax
    import jax.numpy as jnp

    from torchft_tpu.ops import selective_scan as ss

    if jax.devices()[0].platform != "tpu":
        return 2
    tol = cell.traffic["check"]["tolerances"]

    def reading(name):
        jax.clear_caches()
        got = bare.compare(bare.system_answers(adapter, cfg, sample, seq), ref, tol)
        print(json.dumps({"variant": name, **got}), flush=True)

    reading("program")
    ss.STATE_DTYPE = jnp.bfloat16
    reading("bf16_state")
    ss.STATE_DTYPE = jnp.float32
    undo = no_carry(ss)
    reading("zeroed_carry")
    undo()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
