"""A gradient program in stages: loss and every gradient of a scanned
decoder as a CHAIN of jitted programs, so that a caller can send each part of
the gradient on its way (``Manager.allreduce``) while the device computes the
next. One jitted ``value_and_grad`` hands out nothing before it ends; here the
head's gradient is out after the forward pass, and each segment of layers'
as the backward pass reaches it.

A model kind describes itself as three :class:`Stages` functions over the
parameter layout ``{"embed": ..., "layers": <leaves stacked over layers>,
**head leaves}``; :func:`staged_value_and_grad` turns them into

- program A: embedding, the layers' forward pass keeping each layer's INPUT
  (what ``remat="full"`` keeps), head and loss, and the head's backward
  pass: loss, stats, the head leaves' gradient, the cotangent of the last
  hidden state and of what the layers emitted;
- program B, once a segment of consecutive layers, last segment first: for
  each of its layers, the layer recomputed from its kept input and the
  cotangent pulled back through it. One forward, one recomputation, one
  backward: full remat's FLOPs. It reads the whole stacked leaves and picks
  layer ``l0 + i`` inside its loop (a slice outside would be a copy of it a
  step), and ``l0`` is an argument, so segments of one length share one
  executable;
- program E: the embedding's gradient from the cotangent that is left; it
  travels with the last segment's tree;
- ``assemble``, for inside the caller's jitted update: the reduced parts
  back into one tree shaped like the parameters.

A kind whose layers run several times over ONE set of weights
(``Stages.loops`` > 1: models/ouro.py) has a layer's gradient whole only when
the backward pass has gone through the stack once for every pass. Its chain
keeps an input per layer APPLICATION, runs every exit's head in program A
and emits there the head leaves whose gradient is whole then; the backward
of the last passes adds into one gradient tree, segment by segment and in
place (donated), and emits nothing; the backward of pass 1 emits each
segment's accumulated sum as above. What stands between two passes
(``Stages.between``: a final norm every pass boundary reads) is pulled back
by a program of its own at each boundary, and the leaves it reads travel
with the last part. One pass is the chain above, program for program.

A kind without stages (the hybrid: runs of unlike layers and a tied head,
whose gradient is whole only at the very end) is the degenerate chain of ONE
program, ``value_and_grad`` of its loss function: the same calls, one part.
Only ``remat="full"`` is staged.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from torchft_tpu.bucketing import SEGMENT_FLOOR_BYTES

__all__ = ["Stages", "segments", "staged_value_and_grad"]


class Stages(NamedTuple):
    """A model kind as the functions a staged gradient composes.

    ``embed(embed, tokens)`` -> h [B, S, dim] from the embedding matrix;
    ``layer(h, layer_params)``
    -> ``(h, emitted)``, the scanned body with whatever it hands the head
    per layer (None for nothing); ``head(head_params, h, emitted, targets)``
    -> ``(loss, stats)`` from the last hidden state, ``emitted`` stacked over
    layers, and the parameters that are neither ``embed`` nor ``layers``.

    ``loops`` > 1 or ``between``: a kind whose layers run ``loops`` times
    over the same weights. ``between(late, u)`` -> what a pass's last hidden
    state ``u`` becomes for the exit that reads it and the pass that follows,
    ``late`` the head leaves named in ``between_reads``; ``head`` then gets
    the other head leaves, every exit's state stacked ``[loops, B, S, dim]``
    as ``h`` and None as ``emitted`` (such a kind's layers emit nothing)."""

    embed: Callable[..., Any]
    layer: Callable[..., Any]
    head: Callable[..., Any]
    loops: int = 1
    between: Optional[Callable[..., Any]] = None
    between_reads: Tuple[str, ...] = ()


def segments(
    n_layers: int, layer_bytes: int, floor_bytes: int = SEGMENT_FLOOR_BYTES
) -> List[Tuple[int, int]]:
    """``(l0, n)`` of each segment, in the order the backward pass reaches
    them (top layers first): whole layers, as fine as they allow, merged
    from the top down only until a segment's gradient bytes reach
    ``floor_bytes``; what is left at the bottom is the (shorter) last one."""
    per = max(1, -(-floor_bytes // max(layer_bytes, 1)))  # layers a segment
    return [(max(hi - per, 0), hi - max(hi - per, 0))
            for hi in range(n_layers, 0, -per)]


def _nbytes(tree: Any) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(tree))


def _pick(tree: Any, l: Any) -> Any:
    return jax.tree_util.tree_map(
        lambda x: jax.lax.dynamic_index_in_dim(x, l, 0, keepdims=False), tree)


def staged_value_and_grad(
    stages: Optional[Stages],
    loss_fn: Callable[..., Any],
    shardings: Any = None,
    floor_bytes: int = SEGMENT_FLOOR_BYTES,
    frozen: Tuple[str, ...] = (),
) -> Tuple[Callable[..., Any], Callable[[Sequence[Any]], Any]]:
    """-> ``(run, assemble)``.

    ``run(params, tokens, targets, emit)`` dispatches the chain without
    waiting for any of it and returns ``(loss, stats)`` (device values). It
    calls ``emit(part)`` right after dispatching the program that made
    ``part``, a tree of gradient leaves, and BEFORE dispatching the next: a
    device runs what it is handed in order, so whatever ``emit`` enqueues
    (an allreduce's capture and its transfers) sits in front of the next
    segment's backward pass and runs under it. The parts come in one order
    whatever the values: the head leaves; each segment's
    ``{"layers": ...}`` (leaves ``[n, ...]``), the last one with
    ``"embed"`` (and what ``Stages.between`` reads). A kind whose layers run
    several times emits the head leaves, then nothing while the backward goes
    through its last passes, then the segments.
    ``assemble(parts)``, the parts (or same-shaped stand-ins,
    e.g. their reduced copies) in that order -> the gradient tree.

    ``loss_fn(params, tokens, targets) -> (loss, stats)``: what the chain
    equals ``jax.value_and_grad(loss_fn, has_aux=True)`` of, and what runs,
    as one program and one part, where ``stages`` is None. ``shardings``: a
    tree like the parameters of the sharding each gradient leaf is pinned to
    (a stacked leaf's holds for any number of layers). ``frozen``: top-level
    keys of ``params`` that are state and not parameters
    (``models.ModelFns.frozen``): the loss reads them, no part holds a
    gradient for them; a kind with stages has none."""
    if stages is None:
        from torchft_tpu.models import split_frozen

        @jax.jit
        def trainable_grad(trainable, held, tokens, targets):
            return jax.value_and_grad(
                lambda t: loss_fn({**t, **held}, tokens, targets), has_aux=True)(trainable)

        def run_trainable(params, tokens, targets, emit):
            out, grads = trainable_grad(*split_frozen(params, frozen), tokens, targets)
            emit(grads)
            return out

        return run_trainable, lambda parts: parts[0]
    if frozen:
        raise ValueError(f"frozen leaves {frozen} with stages: a staged chain "
                         "differentiates every leaf of its three stages")

    def pin(tree: Any, where: Callable[[Any], Any]) -> Any:
        if shardings is None:
            return tree
        return jax.lax.with_sharding_constraint(tree, where(shardings))

    def head_of(params: Dict[str, Any]) -> Dict[str, Any]:
        return {k: v for k, v in params.items() if k not in ("embed", "layers")}

    @jax.jit
    def forward_and_head(params, tokens, targets):
        def keep_input(h, w):
            out, emitted = stages.layer(h, w)
            return out, (h, emitted)

        h, (kept, emitted) = jax.lax.scan(
            keep_input, stages.embed(params["embed"], tokens), params["layers"])
        loss, pull, stats = jax.vjp(
            lambda hp, h, em: stages.head(hp, h, em, targets),
            head_of(params), h, emitted, has_aux=True)
        g_head, g_h, g_emitted = pull(jnp.ones((), loss.dtype))
        return (loss, stats), pin(g_head, head_of), (g_h, kept, g_emitted)

    @partial(jax.jit, static_argnames="n", donate_argnames=("g_h", "acc"))
    def segment_backward(layers, kept, g_emitted, g_h, l0, n, k0=None, acc=None):
        """``k0``: where the segment's first kept input stands, if not at
        ``l0`` (a later pass's); ``acc``: the segment's gradient from the
        passes the backward has been through, added to in place."""
        def pull_layer(g_h, i):
            _, pull = jax.vjp(
                stages.layer, _pick(kept, (l0 if k0 is None else k0) + i),
                _pick(layers, l0 + i))
            g_h, g_w = pull((g_h, _pick(g_emitted, l0 + i)))
            return g_h, g_w

        g_h, g_layers = jax.lax.scan(
            pull_layer, g_h, jnp.arange(n), reverse=True)
        if acc is not None:
            g_layers = jax.tree_util.tree_map(jnp.add, acc, g_layers)
        return g_h, pin(g_layers, lambda s: s["layers"])

    @jax.jit
    def embed_backward(embed, tokens, g_h):
        _, pull = jax.vjp(lambda e: stages.embed(e, tokens), embed)
        return pin(pull(g_h)[0], lambda s: s["embed"])

    def late_of(params: Dict[str, Any]) -> Dict[str, Any]:
        return {k: params[k] for k in stages.between_reads}

    def early_of(params: Dict[str, Any]) -> Dict[str, Any]:
        return {k: v for k, v in head_of(params).items()
                if k not in stages.between_reads}

    @jax.jit
    def forward_and_exits(params, tokens, targets):
        """Program A of a kind whose layers run ``loops`` times: the kept
        inputs ``[loops * L, ...]`` pass by pass, what each pass's last layer
        gave (``us``), every exit's head and loss and their backward."""
        def keep_input(h, w):
            return stages.layer(h, w)[0], h

        def one_pass(h, _):
            u, kept = jax.lax.scan(keep_input, h, params["layers"])
            h = stages.between(late_of(params), u)
            return h, (kept, u, h)

        _, (kept, us, hs) = jax.lax.scan(
            one_pass, stages.embed(params["embed"], tokens), None, length=stages.loops)
        loss, pull, stats = jax.vjp(
            lambda hp, hs: stages.head(hp, hs, None, targets),
            early_of(params), hs, has_aux=True)
        g_head, g_hs = pull(jnp.ones((), loss.dtype))
        kept = jax.tree_util.tree_map(lambda x: x.reshape(-1, *x.shape[2:]), kept)
        return (loss, stats), pin(g_head, early_of), (g_hs, kept, us)

    @partial(jax.jit, donate_argnames=("g_h", "g_late"))
    def boundary_backward(late, us, g_hs, t, g_h=None, g_late=None):
        """The cotangent of what pass ``t`` handed on (its exit's, and the
        next pass's ``g_h`` where there is one) pulled back through
        ``between``: that of the pass's last layer's output, and ``g_late``
        with this boundary's share of the late leaves' gradient."""
        g = _pick(g_hs, t) if g_h is None else _pick(g_hs, t) + g_h
        _, pull = jax.vjp(stages.between, late, _pick(us, t))
        d_late, g_u = pull(g)
        if g_late is not None:
            d_late = jax.tree_util.tree_map(jnp.add, g_late, d_late)
        return g_u, pin(d_late, late_of)

    def run_looped(params, tokens, targets, emit):
        """``run`` for ``stages.loops`` passes over one stack."""
        layers, late = params["layers"], late_of(params)
        n_layers = jax.tree_util.tree_leaves(layers)[0].shape[0]
        out, g_head, (g_hs, kept, us) = forward_and_exits(params, tokens, targets)
        emit(g_head)
        del g_head
        jax.block_until_ready(jax.tree_util.tree_leaves(params)[:1])  # as in ``run``
        plan = segments(n_layers, _nbytes(layers) // n_layers, floor_bytes)
        acc: List[Any] = [None] * len(plan)  # a segment's gradient so far
        g_h = g_late = None
        for t in reversed(range(stages.loops)):
            g_h, g_late = boundary_backward(late, us, g_hs, t, g_h=g_h, g_late=g_late)
            for s, (l0, n) in enumerate(plan):
                g_h, acc[s] = segment_backward(
                    layers, kept, None, g_h, l0, n=n, k0=t * n_layers + l0, acc=acc[s])
                if t == 0:  # whole: every pass has added its share
                    part = {"layers": acc[s]}
                    if l0 == 0:
                        part["embed"] = embed_backward(params["embed"], tokens, g_h)
                        part.update(g_late)
                    emit(part)
                    acc[s] = None
                    del part
        return out

    def run(params, tokens, targets, emit):
        layers = params["layers"]
        n_layers = jax.tree_util.tree_leaves(layers)[0].shape[0]
        out, g_head, (g_h, kept, g_emitted) = forward_and_head(
            params, tokens, targets)
        emit(g_head)
        del g_head
        # A runtime reserves a program's outputs when the program is
        # ENQUEUED, so a chain enqueued in one go stands in memory whole,
        # beside whatever the caller's previous update has not let go of
        # yet (the gradients it donated: a whole tree). The parameters are
        # that update's outputs: once they are there, it has. The device has
        # program A to run meanwhile, many times the wait.
        jax.block_until_ready(jax.tree_util.tree_leaves(params)[:1])
        plan = segments(n_layers, _nbytes(layers) // n_layers, floor_bytes)
        for l0, n in plan:
            g_h, g_layers = segment_backward(
                layers, kept, g_emitted, g_h, l0, n=n)
            part = {"layers": g_layers}
            if l0 == 0:
                part["embed"] = embed_backward(params["embed"], tokens, g_h)
            emit(part)
            del part, g_layers
        return out

    def assemble(parts: Sequence[Any]) -> Any:
        head, *segs = parts
        stacked = jax.tree_util.tree_map(
            lambda *xs: xs[0] if len(xs) == 1 else jnp.concatenate(xs),
            *(seg["layers"] for seg in reversed(segs)))
        return {**head, "layers": stacked,
                **{k: v for k, v in segs[-1].items() if k != "layers"}}

    looped = stages.loops > 1 or stages.between is not None
    return (run_looped if looped else run), assemble
