"""How tests/chipbench/data/ring.spans.json and ring.summary.json were
recorded (on the CPU, by hand: ``JAX_PLATFORMS=cpu python3
tests/chipbench/record_ring.py``): one replica group of the managed trainer
at tiny widths under a lighthouse, started through chipbench/worker.py with
a trace directory, four steps, the bucket cap at 0.5 MB so that a step has
eight buckets. Kept: the Manager's span ring (``dump_trace``) cut to the
spans of steps 2 and 3, and the SUMMARY line's object. The times are a CPU's
and mean nothing; the tests read structure (which span pairs with which)
and arithmetic. ``ring.*`` is PR 24's program (the unpack worker divides, then
lands: ``divide`` before ``h2d``); ``ring25.*`` was recorded by PR 27 with
``python3 tests/chipbench/record_ring.py ring25`` on the program since PR 25
(``h2d``, then the AVG of the landed leaves)."""

import glob
import json
import os
import subprocess
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)


def main(name: str = "ring") -> None:
    from chipbench_helpers import TINY, read, write
    from torchft_tpu.coordination import LighthouseServer

    tmp = tempfile.mkdtemp()
    cfg = read(os.path.join(ROOT, "chipbench", "configs", "internlm2-1.8b.json"))
    cfg.update(TINY, name="ring-fixture", max_position_embeddings=128)
    cfg["recipe"]["attention"] = "xla"
    write(os.path.join(tmp, "cfg.json"), cfg)
    lh = LighthouseServer(bind="127.0.0.1:0", min_replicas=1,
                          join_timeout_ms=200, quorum_tick_ms=20)
    addr = f"127.0.0.1:{lh.port}"
    try:
        out = subprocess.run(
            [sys.executable, os.path.join(ROOT, "chipbench", "worker.py"),
             "--chipbench-config", os.path.join(tmp, "cfg.json"),
             "--chipbench-trace", os.path.join(tmp, "trace"),
             "--steps", "4", "--batch-size", "2", "--seq-len", "32",
             "--virtual-chips", "1"],
            capture_output=True, text=True, check=True,
            env=dict(os.environ, TORCHFT_LIGHTHOUSE=addr, REPLICA_GROUP_ID="0",
                     TORCHFT_BUCKET_CAP_MB="0.5", JAX_PLATFORMS="cpu")).stdout
    finally:
        lh.shutdown()
    ring = read(glob.glob(os.path.join(tmp, "trace", "*.spans.json"))[0])
    ring["spans"] = [s for s in ring["spans"] if s["step"] in (2, 3)]
    line = next(ln for ln in out.splitlines() if " SUMMARY " in ln)
    data = os.path.join(HERE, "data")
    write(os.path.join(data, f"{name}.spans.json"), ring)
    write(os.path.join(data, f"{name}.summary.json"),
          json.loads(line.split(" SUMMARY ", 1)[1]))
    print(len(ring["spans"]), "spans")


if __name__ == "__main__":
    main(*sys.argv[1:2])
