"""The process the launcher starts for each replica group: has the
configuration's adapter (chipbench/adapters/; ``llama`` where the file names
none) register the cell's configuration with the program under its name and
then runs the adapter's trainer (``examples/train_llama_hsdp.py``) as
``__main__`` — the user's path, no side script, no edit to the program.

    python3 chipbench/worker.py --chipbench-config <file> \
        [--chipbench-trace <dir>] [--chipbench-freeze <group>:<step>:<file>] \
        <the trainer's own arguments>

With ``--chipbench-trace`` (a traced run only) it also (a) records the
device trace of this process from its first step line to its SUMMARY line,
(b) stamps the calls the trainer makes into the Manager (start_quorum,
allreduce, should_commit) on the epoch clock, from this file, around the
call, and (c) asks the Manager for its span ring (``dump_trace``, a public
method) before it shuts down. An untraced run touches none of this.

With ``--chipbench-freeze`` (the failure cell only) replica group <group>
stops itself (SIGSTOP) the moment its commit line for a step >= <step> has
left it, once: <file> is made first, and a process that finds it does not
stop, so the replacement the launcher starts with the same arguments runs
on. The job kind kills the stopped process. A kill from outside alone races
with the victim's request for the next quorum (``_Lines``, below).
"""

import json
import os
import re
import runpy
import signal
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRAINER = os.path.join(REPO, "examples", "train_llama_hsdp.py")


def llama_config(cfg: dict):
    """The configuration file (Hugging Face keys) as the program's
    LlamaConfig; refuses what that code cannot express."""
    import jax.numpy as jnp

    from torchft_tpu.models.llama import LlamaConfig

    if cfg["hidden_size"] != cfg["num_attention_heads"] * cfg["head_dim"]:
        raise ValueError("LlamaConfig derives head_dim = dim / n_heads")
    if cfg.get("sliding_window") or cfg.get("rope_scaling") \
            or cfg.get("tie_word_embeddings") or cfg.get("bias"):
        raise ValueError("sliding window, rope scaling, tied head and biases "
                         "are not in models/llama.py")
    return LlamaConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        ffn_hidden=cfg["intermediate_size"],
        max_seq_len=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
            cfg["recipe"]["param_dtype"]],
    )


class _Tracer:
    """Device trace + host stamps of one worker, written under ``out``."""

    def __init__(self, out: str) -> None:
        self.out = out
        self.spans = []  # [name, t0_epoch_ns, t1_epoch_ns]
        self.meta = {"pid": os.getpid(),
                     "replica": int(os.environ.get("REPLICA_GROUP_ID", 0))}
        self.tag = f"g{self.meta['replica']}_p{os.getpid()}"
        self.tracing = False
        self._mark = None  # (name of the open loop phase, its start)

    # -- the trainer's loop, cut at the three calls it makes into the Manager
    def phase(self, name: str) -> None:
        now = time.time_ns()
        if self._mark is not None:
            self.spans.append([self._mark[0], self._mark[1], now])
        self._mark = (name, now)

    def wrap_manager(self) -> None:
        from torchft_tpu.manager import Manager

        tr = self

        def around(method, before, after):
            inner = getattr(Manager, method)

            def call(self, *a, **kw):
                tr.phase(before)
                try:
                    return inner(self, *a, **kw)
                finally:
                    tr.phase(after)
            setattr(Manager, method, call)

        around("start_quorum", "trainer.start_quorum", "trainer.grad_dispatch")
        # the trainer waits on the returned work before it votes
        around("allreduce", "trainer.allreduce_call", "trainer.allreduce_wait")
        around("should_commit", "trainer.should_commit", "trainer.update_log")
        shutdown = Manager.shutdown

        def dump_then_shutdown(self, *a, **kw):
            self.dump_trace(os.path.join(tr.out, f"{tr.tag}.spans.json"))
            return shutdown(self, *a, **kw)
        Manager.shutdown = dump_then_shutdown

    # -- the profiler, switched by the lines the trainer prints
    def on_line(self, line: str) -> None:
        if not self.tracing and " iter_s=" in line:
            self.start()
        elif self.tracing and " SUMMARY " in line:
            self.stop()

    def start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(os.path.join(self.out, self.tag),
                                 profiler_options=opts)
        self.tracing = True
        # one annotation whose epoch time is known: the trace's own clock
        # starts at 0, this puts the host stamps on it
        self.meta["anchor_epoch_ns"] = time.time_ns()
        with jax.profiler.TraceAnnotation("chipbench.anchor"):
            pass

    def stop(self) -> None:
        import jax

        self.meta["stop_epoch_ns"] = time.time_ns()
        jax.profiler.stop_trace()
        self.tracing = False
        self.phase("trainer.teardown")
        with open(os.path.join(self.out, f"{self.tag}.host.json"), "w") as f:
            json.dump({**self.meta, "spans": self.spans}, f)


class _Tee:
    """stdout that shows every completed line to the tracer."""

    def __init__(self, real, tracer: _Tracer) -> None:
        self.real, self.tracer, self.buf = real, tracer, ""

    def write(self, s: str) -> int:
        n = self.real.write(s)
        self.buf += s
        while "\n" in self.buf:
            line, self.buf = self.buf.split("\n", 1)
            self.tracer.on_line(line)
        return n

    def __getattr__(self, name):
        return getattr(self.real, name)


class _Lines:
    """stdout whose every completed line leaves this process in ONE write.
    The launcher runs its workers unbuffered, so a ``print`` is two writes,
    the text and then the newline, on a pipe that four workers, their
    Managers' native servers and the lighthouse share: whatever another
    writer sends between the two is glued to the text, and the launcher's
    reader (launch.py) saw one line where there were two. Five of the 434
    replica lines in nine runs of the failure cell on the chip were glued so
    (PERF.md section 6, PR 27); a SUMMARY line glued to anything is no JSON,
    a step or SUMMARY line glued behind another replica's is lost, and
    either ends the run with no result. A write of up to PIPE_BUF (4096)
    bytes is atomic.

    ``freeze=(step, marker)``: the scripted failure's victim. Once its commit
    line for a step >= ``step`` is out, the process stops itself where it
    stands, at the step boundary, and the job kind (jobs/kill_rejoin.py)
    SIGKILLs it there. Killed from outside while it runs on, the victim has
    some milliseconds to ask for the next quorum first; where it wins, the
    survivors' next step is formed with a dead member, is discarded, and one
    survivor sits out a timeout (60 s of 120 in the CPU rehearsal with the
    kill 5 ms late; the chip cell's is 600 s): no result either way."""

    COMMIT = re.compile(r"^\[replica \d+\] step=(\d+) inner=")

    def __init__(self, real, freeze=None) -> None:
        self.real, self.buf, self.lock = real, "", threading.Lock()
        self.freeze = freeze

    def write(self, s: str) -> int:
        with self.lock:
            self.buf += s
            whole, nl, self.buf = self.buf.rpartition("\n")
            if nl:
                stop = self.freeze and any(
                    (m := self.COMMIT.match(ln)) and int(m[1]) >= self.freeze[0]
                    for ln in whole.split("\n"))
                if stop:  # the marker before the line: the kill follows the line
                    open(self.freeze[1], "w").close()
                    self.freeze = None
                self.real.write(whole + nl)
                if stop:
                    self.real.flush()
                    os.kill(os.getpid(), signal.SIGSTOP)
        return len(s)

    def flush(self) -> None:
        with self.lock:
            if self.buf:  # a prompt: text that no newline has followed yet
                self.real.write(self.buf)
                self.buf = ""
        self.real.flush()

    def __getattr__(self, name):
        return getattr(self.real, name)


def freeze_of(flag: "str | None"):
    """``<group>:<step>:<file>`` -> (step, file) in the process that is to
    stop itself: replica group <group>, and <file> not there yet (the
    replacement of a victim that did stop finds it)."""
    if not flag:
        return None
    group, step, marker = flag.split(":", 2)
    mine = int(os.environ.get("REPLICA_GROUP_ID", -1)) == int(group)
    return (int(step), marker) if mine and not os.path.exists(marker) else None


def main() -> None:
    argv = sys.argv[1:]
    own = {}
    for flag in ("--chipbench-config", "--chipbench-trace", "--chipbench-freeze"):
        if flag in argv:
            i = argv.index(flag)
            own[flag] = argv[i + 1]
            del argv[i:i + 2]
    sys.path.insert(0, REPO)
    with open(own["--chipbench-config"]) as f:
        cfg = json.load(f)
    from chipbench import manifest

    # what is the architecture's own: its config object, its registration
    # and its trainer (chipbench/adapters/<name>.py; absent key: llama)
    trainer, select = manifest.adapter_for(own["--chipbench-config"], cfg).register(cfg)
    # traced or not: what launch.py reads
    sys.stdout = _Lines(sys.stdout, freeze_of(own.get("--chipbench-freeze")))
    if "--chipbench-trace" in own:
        os.makedirs(own["--chipbench-trace"], exist_ok=True)
        tracer = _Tracer(own["--chipbench-trace"])
        tracer.wrap_manager()
        sys.stdout = _Tee(sys.stdout, tracer)
    sys.argv = [trainer, *select, *argv]
    runpy.run_path(trainer, run_name="__main__")


if __name__ == "__main__":
    main()
