"""The benchmark's command: one run of one cell.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of stdout is the result (chipbench/result.py). No TPU, or
fewer chips than the cell asks for: no result, exit code 2. This process
never touches JAX in the cells that start workers (a chip belongs to one
process); in the ``bare`` cell it is the process that holds the chip.
Everything it writes goes under ``chiprun_out/chipbench/`` (logs, traces),
``.chipbench_cache/`` (the reference's answers, the step times seen) and
the compile cache, all inside the checkout.
"""

import argparse
import json
import os
import shutil
import signal
import sys
import time

T_START = time.monotonic()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import manifest, result  # noqa: E402


def layer_values(cell, obs: dict) -> dict:
    """Every per-layer metric of the cell through its own reducer; a reducer
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in cell.per_layer:
        spec = cell.layer_metric(m["name"])
        out[m["name"]] = cell.reducer(spec["reducer"]).reduce(
            obs, cell, **spec.get("args", {}))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))  # finally: stop workers
    try:
        import torchft_tpu.launcher  # noqa: F401  (the system under test)
        from torchft_tpu.utils import compilation_cache_dir
    except ImportError as e:
        print(f"chipbench: not a torchft_tpu checkout ({e})", file=sys.stderr)
        return 2
    bench = manifest.load(ROOT)
    cell = manifest.Cell(ROOT, bench, args.workload)
    seconds = args.seconds if args.seconds is not None else bench["run_seconds"]
    out_dir = os.path.join(ROOT, "chiprun_out", "chipbench",
                           f"{cell.name}.s{args.seed}.t{args.trace}")
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    compilation_cache_dir()  # JAX_COMPILATION_CACHE_DIR or <checkout>/.jax_cache
    try:
        obs = cell.job().run(
            cell, seed=args.seed, seconds=seconds, trace=bool(args.trace),
            out_dir=out_dir, cache_dir=os.path.join(ROOT, ".chipbench_cache"),
            t_start=T_START)
        values = layer_values(cell, obs) if args.trace else obs["e2e"]
        line = result.build(cell, obs, values, bool(args.trace))
    except Exception as e:  # noqa: BLE001 - any failure: no result line
        import traceback

        traceback.print_exc()
        print(f"chipbench: {cell.name} failed: {type(e).__name__}: {e}",
              file=sys.stderr)
        return 2
    with open(os.path.join(out_dir, "observed.json"), "w") as f:
        json.dump({"e2e": obs["e2e"], "phases": obs["phases"],
                   "notes": obs["notes"], "layer": values if args.trace else None,
                   "trace": {k: v[:20] if isinstance(v, list) else v
                             for k, v in obs.get("trace", {}).items()
                             if k != "ops"}}, f, default=str)
    print("observed: " + json.dumps({"e2e": obs["e2e"], "phases": obs["phases"],
                                     "notes": obs["notes"]}, default=str))
    print(result.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
