"""The process the launcher starts for each replica group: registers the
cell's configuration in ``models.llama.CONFIGS`` under its name and then runs
``examples/train_llama_hsdp.py`` as ``__main__`` — the user's path, no side
script, no edit to the program.

    python3 chipbench/worker.py --chipbench-config <file> \
        [--chipbench-trace <dir>] <the trainer's own arguments>

With ``--chipbench-trace`` (a traced run only) it also (a) records the
device trace of this process from its first step line to its SUMMARY line,
(b) stamps the calls the trainer makes into the Manager (start_quorum,
allreduce, should_commit) on the epoch clock, from this file, around the
call, and (c) asks the Manager for its span ring (``dump_trace``, a public
method) before it shuts down. An untraced run touches none of this.
"""

import json
import os
import runpy
import sys
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


def main() -> None:
    argv = sys.argv[1:]
    own = {}
    for flag in ("--chipbench-config", "--chipbench-trace"):
        if flag in argv:
            i = argv.index(flag)
            own[flag] = argv[i + 1]
            del argv[i:i + 2]
    sys.path.insert(0, REPO)
    with open(own["--chipbench-config"]) as f:
        cfg = json.load(f)
    from torchft_tpu.models.llama import CONFIGS

    CONFIGS[cfg["name"]] = llama_config(cfg)
    if "--chipbench-trace" in own:
        os.makedirs(own["--chipbench-trace"], exist_ok=True)
        tracer = _Tracer(own["--chipbench-trace"])
        tracer.wrap_manager()
        sys.stdout = _Tee(sys.stdout, tracer)
    sys.argv = [TRAINER, "--config", cfg["name"], *argv]
    runpy.run_path(TRAINER, run_name="__main__")


if __name__ == "__main__":
    main()
