"""Streaming DiLoCo training example (reference: train_diloco.py).

Each replica group trains a multi-layer MLP locally with AdamW and
synchronizes pseudo-gradients every ``--sync-every`` steps through the
fault-tolerant manager, with the model split into fragments that sync
staggered (streaming DiLoCo). Run the demo:

    python examples/train_diloco.py --demo
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def train(args) -> None:
    if args.virtual_chips:
        # local multi-process runs share no TPU; use a virtual CPU platform
        from torchft_tpu.utils import force_virtual_cpu_devices

        force_virtual_cpu_devices(args.virtual_chips)

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from torchft_tpu.local_sgd import DiLoCo
    from torchft_tpu.manager import Manager
    from torchft_tpu.process_group import ProcessGroupHost

    replica_id = int(os.environ.get("REPLICA_GROUP_ID", args.replica_id))
    lighthouse = os.environ.get("TORCHFT_LIGHTHOUSE", args.lighthouse)

    # multi-layer MLP (the reference uses MultiMLP split via pipelining into
    # fragments; fragments here are pytree partitions)
    def init_params(key):
        dims = [32, 64, 64, 64, 10]
        keys = jax.random.split(key, len(dims) - 1)
        return {
            f"layer{i}": {
                "w": jax.random.normal(keys[i], (dims[i], dims[i + 1]), jnp.float32)
                * (1.0 / np.sqrt(dims[i])),
                "b": jnp.zeros((dims[i + 1],), jnp.float32),
            }
            for i in range(len(dims) - 1)
        }

    def forward(params, x):
        h = x
        n = len(params)
        for i in range(n):
            layer = params[f"layer{i}"]
            h = h @ layer["w"] + layer["b"]
            if i < n - 1:
                h = jax.nn.relu(h)
        return h

    def loss_fn(params, x, y):
        logits = forward(params, x)
        return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()

    params = init_params(jax.random.PRNGKey(replica_id))
    inner_tx = optax.adamw(1e-3)
    inner_state = inner_tx.init(params)

    state = {"params": params, "inner": inner_state}

    def load_state(sd):
        state["params"] = jax.tree_util.tree_map(jnp.asarray, sd["params"])

    def save_state():
        return {"params": state["params"]}

    manager = Manager(
        pg=ProcessGroupHost(timeout=30.0),
        load_state_dict=load_state,
        state_dict=save_state,
        min_replica_size=args.min_replica_size,
        use_async_quorum=False,  # DiLoCo requirement
        replica_id=f"train_diloco_{replica_id}",
        lighthouse_addr=lighthouse,
        timeout=30.0,
    )

    diloco = DiLoCo(
        manager,
        state["params"],
        outer_tx=optax.sgd(args.outer_lr, momentum=0.9, nesterov=True),
        sync_every=args.sync_every,
        num_fragments=args.num_fragments,
        fragment_sync_delay=args.fragment_sync_delay,
        fragment_update_alpha=args.fragment_update_alpha,
        # a live heal rebinds state["params"]; DiLoCo must re-read them
        # instead of computing pseudogradients from stale pre-heal leaves
        get_params=lambda: state["params"],
    )

    rng = np.random.RandomState(replica_id)

    def _inner(params, opt_state, x, y):
        loss, grads = jax.value_and_grad(loss_fn)(params, x, y)
        updates, opt_state = inner_tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    inner_step = jax.jit(_inner)

    target_outer_steps = args.steps // args.sync_every * args.num_fragments
    local = 0
    try:
        while manager.current_step() < target_outer_steps:
            x = jnp.asarray(rng.randn(args.batch_size, 32), jnp.float32)
            y = jnp.asarray(rng.randint(0, 10, size=(args.batch_size,)))
            state["params"], state["inner"], loss = inner_step(
                state["params"], state["inner"], x, y
            )
            state["params"] = diloco.step(state["params"])
            local += 1
            if local % args.sync_every == 0:
                print(
                    f"[replica {replica_id}] outer_step={manager.current_step()} "
                    f"local={local} loss={float(loss):.4f}",
                    flush=True,
                )
    finally:
        try:
            # never strand peers on an in-flight commit round, even on
            # interrupted exits; best-effort — a flush failing on a dead
            # wire must not mask the original exception or skip shutdown
            state["params"] = diloco.flush(state["params"])
        except Exception as e:  # noqa: BLE001
            print(f"[replica {replica_id}] flush failed during teardown: {e}",
                  flush=True)
        finally:
            manager.shutdown(wait=False)
    w_sum = sum(
        float(jnp.sum(jnp.abs(diloco.fragments[i].original[0])))
        for i in range(len(diloco.fragments))
    )
    print(f"[replica {replica_id}] done: global_l1[frag0]={w_sum:.6f}", flush=True)


def demo(args) -> None:
    import subprocess

    from torchft_tpu.coordination import LighthouseServer

    lh = LighthouseServer(
        bind="127.0.0.1:0", min_replicas=1, join_timeout_ms=500,
        quorum_tick_ms=50, heartbeat_timeout_ms=2000,
    )
    addr = f"127.0.0.1:{lh.port}"
    print(f"lighthouse at http://{addr}/", flush=True)

    def spawn(rid):
        env = dict(os.environ, TORCHFT_LIGHTHOUSE=addr, REPLICA_GROUP_ID=str(rid))
        return subprocess.Popen(
            [sys.executable, __file__, "--steps", str(args.steps),
             "--batch-size", str(args.batch_size),
             "--sync-every", str(args.sync_every),
             "--num-fragments", str(args.num_fragments),
             "--virtual-chips", "1"],
            env=env,
        )

    procs = {rid: spawn(rid) for rid in range(args.replicas)}
    time.sleep(args.kill_after)
    victim = args.replicas - 1
    print(f"--- killing replica {victim} ---", flush=True)
    procs[victim].kill()
    procs[victim].wait()
    time.sleep(1)
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
    parser.add_argument("--steps", type=int, default=40)
    parser.add_argument("--batch-size", type=int, default=16)
    parser.add_argument("--outer-lr", type=float, default=0.7)
    parser.add_argument("--sync-every", type=int, default=4)
    parser.add_argument("--num-fragments", type=int, default=2)
    parser.add_argument("--fragment-sync-delay", type=int, default=0)
    parser.add_argument("--fragment-update-alpha", type=float, default=0.0)
    parser.add_argument("--virtual-chips", type=int, default=0,
                        help="force N virtual CPU devices (CPU testing only: "
                             "pins the platform to cpu, never a chip run)")
    parser.add_argument("--min-replica-size", type=int, default=1)
    parser.add_argument("--replica-id", type=int, default=0)
    parser.add_argument("--lighthouse", type=str, default="127.0.0.1:29510")
    parser.add_argument("--demo", action="store_true",
                        help="CPU control-flow demo: replicas run with "
                             "--virtual-chips 1 and touch no accelerator")
    parser.add_argument("--replicas", type=int, default=2)
    parser.add_argument("--kill-after", type=float, default=8.0)
    args = parser.parse_args()
    if args.demo:
        demo(args)
    else:
        train(args)
