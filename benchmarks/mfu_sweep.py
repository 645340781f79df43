"""Sweep remat policy x batch size (and splash block size) for the
single-chip Llama bench.

Finds the config that maximizes MFU on the local chip; bench.py's settings
should track the winner. Uses bench.py's `timed_train_step` so the sweep
measures exactly the workload the headline bench reports. Run on TPU
hardware:
    python benchmarks/mfu_sweep.py            # remat x batch x chunk matrix
    python benchmarks/mfu_sweep.py --blocks   # splash block-size sweep

Every config runs in its OWN SUBPROCESS with a wall-clock timeout, so one
config that fails or never finishes compiling costs one timeout, not the
rest of the matrix. A chip belongs to one process at a time: the parent
never opens the runtime (it probes the backend in a child) and the cells
run one after another.
"""

import argparse
import itertools
import os
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = """\
import sys
sys.path.insert(0, {repo!r})
from bench import timed_train_step
from torchft_tpu.models.llama import CONFIGS
from torchft_tpu.ops import attention as _attn
from torchft_tpu.utils import enable_compilation_cache
enable_compilation_cache()
tps, mfu = timed_train_step(CONFIGS[{cfg!r}], {batch}, {seq}, steps=10,
                            remat={remat!r}, loss_chunk={chunk},
                            master_f32={master_f32})
print(f"RESULT {{tps:.1f}} {{mfu:.4f}} {{_attn.LAST_DISPATCH}}", flush=True)
"""


def run_config(cfg, batch, seq, remat, chunk, env_extra, timeout_s,
               master_f32=False):
    """Run one sweep cell in a subprocess; returns a one-line verdict."""
    env = dict(os.environ, **env_extra)
    code = _CHILD.format(repo=REPO, cfg=cfg, batch=batch, seq=seq,
                         remat=remat, chunk=chunk, master_f32=master_f32)
    try:
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=timeout_s)
    except subprocess.TimeoutExpired:
        return None, f"TIMEOUT >{timeout_s:.0f}s"
    for line in reversed(out.stdout.splitlines()):
        if line.startswith("RESULT "):
            _, tps, mfu, dispatch = line.split()
            return (float(tps), float(mfu), dispatch), None
    # surface the actual exception, not whatever JAX printed last: the
    # traceback's final exception line (or an XLA status code) is the root
    # cause; a blind tail usually lands on JAX's frame-filtering notice
    err_text = out.stderr.strip() or out.stdout.strip()
    cause = ""
    for line in reversed(err_text.splitlines()):
        if any(m in line for m in ("Error", "RESOURCE_EXHAUSTED", "INTERNAL",
                                   "INVALID_ARGUMENT", "UNIMPLEMENTED")):
            cause = line.strip()[:300]
            break
    return None, f"FAILED rc={out.returncode}: {cause or err_text[-300:]}"


def backend_alive() -> bool:
    from torchft_tpu.utils import probe_backend

    status, _ = probe_backend(90.0)
    return status in ("accel", "cpu")


def sweep(cells, timeout_s):
    """cells: iterable of (label, env_extra, kwargs for run_config)."""
    for label, env_extra, kw in cells:
        result, err = run_config(env_extra=env_extra, timeout_s=timeout_s, **kw)
        if result:
            tps, mfu, dispatch = result
            print(f"{label}: {tps:10.1f} tok/s  MFU={mfu:.4f}  [{dispatch}]",
                  flush=True)
        else:
            print(f"{label}: {err}", flush=True)
            if err.startswith("TIMEOUT") and not backend_alive():
                print("# backend no longer responds after the timeout — "
                      "stopping the sweep", flush=True)
                return


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--blocks", action="store_true",
                    help="sweep splash block sizes instead of the remat matrix")
    ap.add_argument("--timeout", type=float, default=1200.0,
                    help="per-config wall-clock budget (compile + warmup + "
                         "2 timed 10-step windows)")
    ap.add_argument("--unroll", type=int, default=0,
                    help="set TORCHFT_TPU_SCAN_UNROLL for every cell "
                         "(layer-scan unroll factor; 0 = leave unset)")
    ap.add_argument("--model", default="bench_350m",
                    help="CONFIGS key to bench (default bench_350m, the "
                         "cross-round headline config; bench_1b measures "
                         "the larger-matmul regime on the same chip)")
    ap.add_argument("--seq", type=int, default=2048,
                    help="sequence length (long-context cells: pair a "
                         "longer --seq with a smaller batch and a nonzero "
                         "CHUNK, e.g. --seq 8192 --cell full,2,512)")
    ap.add_argument("--cell", action="append", default=[],
                    metavar="REMAT,BATCH,CHUNK[,mf32]",
                    help="run only these cells (repeatable), e.g. "
                         "--cell full,16,0 --cell attn,8,0 --cell "
                         "full,8,0,mf32 (f32 master weights + moments)")
    args = ap.parse_args()

    # validate cell specs BEFORE the backend probe: an argv typo must cost
    # an argparse error, not a backend start-up in a child
    cell_specs = []
    for spec in args.cell:
        parts = spec.split(",")
        if len(parts) < 3 or (len(parts) == 4 and parts[3] != "mf32") \
                or len(parts) > 4:
            ap.error(f"--cell {spec!r}: expected REMAT,BATCH,CHUNK with "
                     "optional ',mf32' (e.g. full,8,0 or full,8,0,mf32)")
        if parts[0] not in ("dots", "none", "full", "attn"):
            ap.error(f"--cell {spec!r}: REMAT must be one of "
                     "dots/none/full/attn")
        try:
            batch, chunk = int(parts[1]), int(parts[2])
        except ValueError:
            ap.error(f"--cell {spec!r}: BATCH and CHUNK must be integers")
        cell_specs.append((parts[0], batch, chunk, len(parts) > 3))

    # same pre-probe rule for --model: importing CONFIGS imports jax but
    # initializes no backend, so a typo still fails in milliseconds
    from torchft_tpu.models.llama import CONFIGS

    if args.model not in CONFIGS:
        ap.error(f"--model {args.model!r}: not in CONFIGS "
                 f"({', '.join(sorted(CONFIGS))})")

    # share one persistent compilation cache with every child: a re-run of
    # the sweep (or the bench after it) replays cached executables.
    # Exports JAX_COMPILATION_CACHE_DIR, which run_config's children
    # inherit and bench.timed_train_step's caller enables. After argparse:
    # --help must not pay a backend probe.
    from torchft_tpu.utils import compilation_cache_dir, probe_backend

    compilation_cache_dir()

    # probe in a SUBPROCESS: a chip belongs to one process at a time, so
    # the parent must not hold the TPU runtime open while its children run
    status, detail = probe_backend(90.0)
    if status != "accel":
        sys.exit(f"mfu_sweep needs a TPU (probe: {status} {detail})")

    cfg, seq = args.model, args.seq
    if args.unroll:
        # children inherit os.environ through run_config
        os.environ["TORCHFT_TPU_SCAN_UNROLL"] = str(args.unroll)

    def _unroll_tag() -> str:
        # model/seq/unroll are run-scoped, not cell-scoped — they must
        # still be in every label or archived sweep lines from different
        # runs are indistinguishable
        tag = f" unroll={args.unroll}" if args.unroll else ""
        if args.model != "bench_350m":
            tag += f" model={args.model}"
        return tag
    attn = os.environ.get("TORCHFT_TPU_ATTENTION", "auto")

    if cell_specs:
        cells = [
            (f"attn={attn} remat={remat:5s} batch={batch:3d} "
             f"chunk={chunk:4d} seq={seq}"
             + (" master=f32" if mf32 else "") + _unroll_tag(),
             {},
             dict(cfg=cfg, batch=batch, seq=seq, remat=remat,
                  chunk=chunk, master_f32=mf32))
            for remat, batch, chunk, mf32 in cell_specs
        ]
        sweep(cells, args.timeout)
        return

    if args.blocks:
        # uniform tiles first (the headline dimension), then asymmetric
        # q/kv combos around the measured uniform winner (1024): a smaller
        # kv tile relieves VMEM pressure, a larger q tile amortizes the
        # online-softmax bookkeeping. Tiles that don't divide --seq are
        # filtered here — failing them in a child would burn a subprocess
        # on a result knowable in the parent.
        combos = [(blk, blk) for blk in (128, 256, 512, 1024, 2048)]
        combos += [(1024, 512), (1024, 256), (512, 1024), (2048, 512),
                   (2048, 1024)]
        dropped = [(bq, bkv) for bq, bkv in combos
                   if seq % bq != 0 or seq % bkv != 0]
        combos = [(bq, bkv) for bq, bkv in combos
                  if seq % bq == 0 and seq % bkv == 0]
        if dropped:
            print(f"# dropped {len(dropped)} tile combos that don't divide "
                  f"seq={seq}: {dropped}", flush=True)
        if not combos:
            sys.exit(f"--blocks: no tile in the ladder divides seq={seq} "
                     "(tiles are multiples of 128)")
        cells = [
            (f"attn=splash block_q={bq:4d} block_kv={bkv:4d} remat=full "
             f"batch=8 seq={seq}" + _unroll_tag(),
             {"TORCHFT_TPU_ATTENTION": "splash",
              "TORCHFT_TPU_SPLASH_BLOCK": str(bq),
              "TORCHFT_TPU_SPLASH_BLOCK_KV": str(bkv)},
             dict(cfg=cfg, batch=8, seq=seq, remat="full", chunk=0))
            for bq, bkv in combos
        ]
        sweep(cells, args.timeout)
        return

    remats = ["dots", "none", "full", "attn"]
    cells = [
        (f"attn={attn} remat={remat:5s} batch={batch:3d} chunk={chunk:4d} "
         f"seq={seq}" + _unroll_tag(),
         {},
         dict(cfg=cfg, batch=batch, seq=seq, remat=remat, chunk=chunk))
        for remat, batch, chunk in itertools.product(remats, [8, 16, 32], [0, 512])
    ]
    sweep(cells, args.timeout)


if __name__ == "__main__":
    main()
