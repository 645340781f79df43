"""Fault-tolerant data-parallel training example (reference: train_ddp.py).

Each replica group is one process training a small CNN on synthetic
CIFAR-10-shaped data with optax, fault-tolerant across replica groups via
torchft_tpu: per-step quorum, managed allreduce of the grad pytree, two-phase
commit, live recovery over HTTP on rejoin.

Run a 2-replica demo (spawns lighthouse + replicas, kills one mid-run):

    python examples/train_ddp.py --demo

Or run components manually:

    python -m torchft_tpu.lighthouse --bind 0.0.0.0:29510 &
    TORCHFT_LIGHTHOUSE=127.0.0.1:29510 REPLICA_GROUP_ID=0 python examples/train_ddp.py
    TORCHFT_LIGHTHOUSE=127.0.0.1:29510 REPLICA_GROUP_ID=1 python examples/train_ddp.py
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def build_trainer(replica_id: int = 0, batch_size: int = 8, lr: float = 0.01):
    """The example's model/optimizer/step, importable as a unit.

    Returns ``(state, grad_fn, optimizer, make_batch)`` so harnesses can run
    the REAL trainer loop this example trains (benchmarks/ft_overhead_bench.py
    measures its per-step cost bare vs. under a live Manager).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    # -- model: tiny CNN on 32x32x3 inputs --------------------------------
    def init_params(key):
        k1, k2, k3 = jax.random.split(key, 3)
        return {
            "conv": jax.random.normal(k1, (3, 3, 3, 16), jnp.float32) * 0.1,
            "w1": jax.random.normal(k2, (16 * 16 * 16, 64), jnp.float32) * 0.05,
            "w2": jax.random.normal(k3, (64, 10), jnp.float32) * 0.05,
        }

    def forward(params, x):
        h = jax.lax.conv_general_dilated(
            x, params["conv"], window_strides=(2, 2), padding="SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        h = jax.nn.relu(h)
        h = h.reshape(h.shape[0], -1)
        h = jax.nn.relu(h @ params["w1"])
        return h @ params["w2"]

    def loss_fn(params, x, y):
        logits = forward(params, x)
        return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()

    grad_fn = jax.jit(jax.value_and_grad(loss_fn))

    # Different init per replica: init_sync recovers everyone from the primary.
    params = init_params(jax.random.PRNGKey(replica_id))
    optimizer = optax.sgd(lr, momentum=0.9)
    opt_state = optimizer.init(params)
    state = {"params": params, "opt_state": opt_state}

    rng = np.random.RandomState(replica_id)

    def make_batch():
        # synthetic batch, sharded per replica (DistributedSampler equivalent)
        x = jnp.asarray(rng.randn(batch_size, 32, 32, 3), jnp.float32)
        y = jnp.asarray(rng.randint(0, 10, size=(batch_size,)))
        return x, y

    return state, grad_fn, optimizer, make_batch


def train(args) -> None:
    if args.virtual_chips:
        # local multi-process runs share no TPU; use a virtual CPU platform
        from torchft_tpu.utils import force_virtual_cpu_devices

        force_virtual_cpu_devices(args.virtual_chips)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from torchft_tpu.manager import Manager
    from torchft_tpu.process_group import ProcessGroupHost

    replica_id = int(os.environ.get("REPLICA_GROUP_ID", args.replica_id))
    lighthouse = os.environ.get("TORCHFT_LIGHTHOUSE", args.lighthouse)

    state, grad_fn, optimizer, _make_batch = build_trainer(
        replica_id, args.batch_size, args.lr
    )
    opt_state = state["opt_state"]

    def load_state(sd):
        state["params"] = jax.tree_util.tree_map(jnp.asarray, sd["params"])
        state["opt_state"] = jax.tree_util.tree_map(
            lambda t, x: jnp.asarray(x) if hasattr(t, "dtype") else x,
            opt_state, sd["opt_state"],
        )

    def save_state():
        return {"params": state["params"], "opt_state": state["opt_state"]}

    # --transport pg mirrors the reference train_ddp default (PGTransport,
    # train_ddp.py:91-110): healing rides a DEDICATED recovery PG that the
    # Manager re-rendezvouses with every quorum (the host plane forbids
    # mixing p2p and collective traffic on one PG generation, so unlike
    # the reference the recovery PG is a separate instance).
    transport = recovery_pg = None
    if args.transport == "pg":
        from torchft_tpu.checkpointing import PGTransport

        recovery_pg = ProcessGroupHost(timeout=30.0)  # caller-owned
        transport = PGTransport(recovery_pg, timeout=30.0)

    manager = Manager(
        pg=ProcessGroupHost(timeout=30.0),
        load_state_dict=load_state,
        state_dict=save_state,
        min_replica_size=args.min_replica_size,
        replica_id=f"train_ddp_{replica_id}",
        lighthouse_addr=lighthouse,
        timeout=30.0,
        checkpoint_transport=transport,
    )

    rng = np.random.RandomState(replica_id)
    print(f"[replica {replica_id}] starting at step {manager.current_step()}", flush=True)
    try:
        _train_loop(args, manager, state, grad_fn, optimizer, rng, replica_id)
    finally:
        manager.shutdown(wait=False)
        if recovery_pg is not None:
            recovery_pg.shutdown()  # PGTransport.shutdown never touches it


def _train_loop(args, manager, state, grad_fn, optimizer, rng, replica_id) -> None:
    import jax
    import jax.numpy as jnp
    import optax

    accum = max(1, getattr(args, "grad_accum", 1))
    quantize = bool(getattr(args, "quantize", False))
    while manager.current_step() < args.steps:
        # synthetic batch, sharded per replica (DistributedSampler equivalent)
        x = jnp.asarray(rng.randn(args.batch_size, 32, 32, 3), jnp.float32)
        y = jnp.asarray(rng.randint(0, 10, size=(args.batch_size,)))

        manager.start_quorum()
        if accum > 1:
            # Gradient accumulation over the streaming pipeline: each
            # microbatch's streamed allreduce starts reducing its buckets
            # while the NEXT microbatch's grad_fn runs, so the wire rides
            # under compute. Allreduce is linear, so averaging the reduced
            # microbatch means equals reducing the accumulated mean.
            # --quantize streams the same buckets fp8-compressed with
            # error feedback — it no longer drops to the serial
            # unbucketed path (tests/test_examples_smoke.py pins this).
            streams = []
            for k in range(accum):
                if k > 0:
                    x = jnp.asarray(
                        rng.randn(args.batch_size, 32, 32, 3), jnp.float32
                    )
                    y = jnp.asarray(rng.randint(0, 10, size=(args.batch_size,)))
                loss, grads = grad_fn(state["params"], x, y)
                streams.append(
                    manager.allreduce_streamed(
                        grads, should_quantize=quantize
                    )
                )
            reduced_trees = [s.wait(timeout=60) for s in streams]
            reduced = jax.tree_util.tree_map(
                lambda *vs: sum(jnp.asarray(v) for v in vs) / len(vs),
                *reduced_trees,
            )
        else:
            loss, grads = grad_fn(state["params"], x, y)
            reduced = manager.allreduce(
                grads, should_quantize=quantize
            ).get_future().wait(timeout=60)
        if manager.should_commit():
            updates, new_opt_state = optimizer.update(
                jax.tree_util.tree_map(jnp.asarray, reduced),
                state["opt_state"], state["params"],
            )
            state["params"] = optax.apply_updates(state["params"], updates)
            state["opt_state"] = new_opt_state
            print(
                f"[replica {replica_id}] step={manager.current_step()} "
                f"loss={float(loss):.4f} participants={manager.num_participants()}",
                flush=True,
            )
    w_sum = float(jnp.sum(jnp.abs(state["params"]["w2"])))
    print(f"[replica {replica_id}] done: w2_l1={w_sum:.6f}", flush=True)


def demo(args) -> None:
    """Spawn lighthouse + N replicas, kill one mid-run, watch it recover."""
    import subprocess

    from torchft_tpu.coordination import LighthouseServer

    lh = LighthouseServer(
        bind="127.0.0.1:0", min_replicas=1, join_timeout_ms=500,
        quorum_tick_ms=50, heartbeat_timeout_ms=2000,
    )
    addr = f"127.0.0.1:{lh.port}"
    print(f"lighthouse at http://{addr}/ (dashboard)", flush=True)

    def spawn(rid):
        env = dict(os.environ, TORCHFT_LIGHTHOUSE=addr, REPLICA_GROUP_ID=str(rid))
        return subprocess.Popen(
            [sys.executable, __file__, "--steps", str(args.steps),
             "--batch-size", str(args.batch_size),
             "--transport", args.transport,
             "--virtual-chips", "1"],
            env=env,
        )

    procs = {rid: spawn(rid) for rid in range(args.replicas)}
    time.sleep(args.kill_after)
    victim = args.replicas - 1
    print(f"--- killing replica {victim} ---", flush=True)
    procs[victim].kill()
    procs[victim].wait()
    time.sleep(2)
    print(f"--- restarting replica {victim} ---", flush=True)
    procs[victim] = spawn(victim)

    rc = 0
    try:
        for rid, p in procs.items():
            try:
                rc |= p.wait(timeout=300)
            except subprocess.TimeoutExpired:
                # a wedged replica must not orphan its siblings or skip
                # lighthouse shutdown
                print(f"--- replica {rid} wedged; killing ---", flush=True)
                p.kill()
                p.wait()
                rc |= 1
    finally:
        for p in procs.values():
            if p.poll() is None:
                p.kill()
        lh.shutdown()
    print("demo finished rc=", rc, flush=True)
    sys.exit(rc)


if __name__ == "__main__":
    parser = argparse.ArgumentParser()
    parser.add_argument("--steps", type=int, default=20)
    parser.add_argument("--batch-size", type=int, default=8)
    parser.add_argument("--grad-accum", type=int, default=1,
                        help="microbatches per step; >1 issues one STREAMED "
                             "allreduce per microbatch so bucket reduction "
                             "overlaps the next microbatch's grad_fn")
    parser.add_argument("--quantize", action="store_true",
                        help="stream gradient buckets fp8-compressed with "
                             "error feedback (TORCHFT_COMPRESS picks the "
                             "codec); composes with --grad-accum")
    parser.add_argument("--lr", type=float, default=0.01)
    parser.add_argument("--min-replica-size", type=int, default=1)
    parser.add_argument("--transport", choices=["http", "pg"], default="http",
                        help="live-healing transport: http (default) or pg "
                             "(dedicated recovery process group, the "
                             "reference train_ddp default)")
    parser.add_argument("--replica-id", type=int, default=0)
    parser.add_argument("--lighthouse", type=str, default="127.0.0.1:29510")
    parser.add_argument("--virtual-chips", type=int, default=0,
                        help="force N virtual CPU devices (CPU testing only: "
                             "pins the platform to cpu, never a chip run)")
    parser.add_argument("--demo", action="store_true",
                        help="CPU control-flow demo: replicas run with "
                             "--virtual-chips 1 and touch no accelerator")
    parser.add_argument("--replicas", type=int, default=2)
    parser.add_argument("--kill-after", type=float, default=6.0)
    args = parser.parse_args()
    if args.demo:
        demo(args)
    else:
        train(args)
