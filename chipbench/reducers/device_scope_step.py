"""``device_scope`` (which see) in a cell whose job hands out no ``scopes``:
the ``bare`` job traces its fused step and keeps the instructions' names
only. The map from each traced instruction to the scope path the program gave
it is read here, once a run (kept under ``obs["scopes"]``, where the cell's
other scope metrics find it): the step as jobs/bare.py builds it, from the
cell's adapter and recipe, compiled for the shapes the window ran (the same
program: with the compile cache on it is read back, not compiled), and its
text cut by jobs/bare_routed.py's ``scopes_of``. A run that was not traced,
or a program without the scope, leaves nothing to read."""

from chipbench import manifest


def _step_text(cell) -> str:
    import jax
    import jax.numpy as jnp
    import optax

    cfg, recipe = cell.config, cell.config["recipe"]
    adapter = cell.adapter()
    init_, loss_, _ = adapter.program()
    pc = adapter.config(cfg)
    tx = optax.adamw(recipe["lr"], weight_decay=recipe["weight_decay"])

    def init(seed):
        params = init_(jax.random.PRNGKey(seed), pc)
        return params, tx.init(params)

    def step(params, opt_state, tokens):  # jobs/bare.py's, word for word
        loss, grads = jax.value_and_grad(loss_)(
            params, tokens, tokens, pc, remat=recipe["remat"])
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    params, opt_state = jax.eval_shape(init, 0)
    tokens = jax.ShapeDtypeStruct((recipe["batch_size"], recipe["seq_len"]), jnp.int32)
    return jax.jit(step, donate_argnums=(0, 1)).lower(
        params, opt_state, tokens).compile().as_text()


def reduce(obs, cell, scope, also=None, without=None):
    if not obs.get("trace") or not obs.get("steps_in_window"):
        return None
    if "scopes" not in obs:
        obs["scopes"] = manifest.load_module(cell.root, "jobs", "bare_routed").scopes_of(
            _step_text(cell), obs["trace"]["ops"])
    return manifest.load_module(cell.root, "reducers", "device_scope").reduce(
        obs, cell, scope, also=also, without=without)
