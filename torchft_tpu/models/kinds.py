"""The registry of model kinds: what a trainer needs of a kind
(:class:`ModelFns`), the table each kind's module fills where the kind is
defined (:func:`register`) and the one lookup by a configuration's class
(:func:`model_fns`). Imports no model: every kind's module imports this."""

from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

__all__ = ["ModelFns", "register", "model_fns", "logged", "split_frozen"]


class ModelFns(NamedTuple):
    """What :func:`model_fns` hands out for one kind of configuration."""

    init: Callable[..., Any]
    loss: Callable[..., Any]
    param_specs: Callable[..., Any]
    # (cfg, attention_fn) -> models.staged.Stages, or None for a kind whose
    # gradient is one program (staged_value_and_grad's degenerate chain)
    stages: Optional[Callable[..., Any]]
    # top-level keys of the parameter tree that this run does NOT train: a
    # buffer that is state and no parameter (a selection bias), or the whole
    # trunk of a stage that trains a small part beside it (``models/dsa.py``:
    # every key but ``indexer``). The loss reads them; they stay in
    # ``params``, so a state dict, a durable checkpoint and a heal carry
    # every one (a rejoining group needs the trunk as much as the part that
    # learns) and the SUMMARY's ``frozen_checksum`` is their bit sum, which
    # no step may move; the gradient program takes them as a second argument
    # it does not differentiate, so no cotangent, no ``Manager.allreduce``
    # bucket, no optimizer moment, update or weight decay exists for them.
    # WHICH keys they are may depend on the configuration (the same class
    # trains everything in another stage): a kind may register a function
    # ``cfg -> keys`` here, and :func:`model_fns` hands out its value
    frozen: Any = ()


def split_frozen(params: Dict[str, Any], frozen: Tuple[str, ...]
                 ) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """``params`` as (the trainable leaves, the ``frozen`` ones that are
    there): ``{**trainable, **held}`` is ``params`` again."""
    return ({k: v for k, v in params.items() if k not in frozen},
            {k: params[k] for k in frozen if k in params})


# configuration class -> (what makes its ModelFns, its presets by --config name)
_KINDS: Dict[type, Tuple[Callable[[], ModelFns], Dict[str, Any]]] = {}


def register(config_class: type, presets: Dict[str, Any], make: Callable[[], ModelFns]) -> None:
    """Configurations of ``config_class``, and of classes derived from it
    that register nothing of their own, are the kind whose :class:`ModelFns`
    ``make()`` gives (at each lookup: it may import what imports the kind's
    module); ``presets`` are the names ``--config`` knows them by."""
    _KINDS[config_class] = (make, dict(presets))


def model_fns(cfg: Any) -> ModelFns:
    """A configuration object's :class:`ModelFns` by its class, most derived
    first (a LingConfig is an MoEConfig is a LlamaConfig, and is Ling's): the
    one place a trainer learns which model it runs.

    ``init(key, cfg)`` -> parameter pytree; ``param_specs(cfg)`` -> its
    PartitionSpecs; ``frozen``: the top-level keys this configuration does
    not train, a tuple; ``loss(params, tokens, targets, cfg, attention_fn=,
    remat=)`` -> ``(loss, stats)`` for ``value_and_grad(has_aux=True)``,
    ``stats`` mapping a trace instant's name to the device scalars a loop
    fetches beside the loss ({} for a dense model); ``stages(cfg,
    attention_fn)`` -> the same loss (at ``remat="full"``) and stats as the
    functions ``models.staged.staged_value_and_grad`` chains."""
    for cls in type(cfg).__mro__:
        if cls in _KINDS:
            fns = _KINDS[cls][0]()
            return fns._replace(frozen=fns.frozen(cfg)) if callable(fns.frozen) else fns
    raise TypeError(f"{type(cfg).__name__}: no kind of model is registered for it")


def logged(loss_and_stats: Callable[..., Any], **groups: Tuple[str, ...]
           ) -> Callable[..., Any]:
    """``loss_and_stats`` (anything that returns ``(value, stats)``) handing
    out, of its stats, what the trainer logs under the names it logs it by:
    ``moe=("aux_loss",)`` -> ``{"moe_stats": {"moe_aux_loss": ...}}``; a key
    the configuration at hand does not produce is left out, and so is a group
    none of whose keys it produces (a stage's counters, outside that stage)."""
    def loss(*args: Any, **kw: Any) -> Tuple[Any, Dict[str, Any]]:
        value, stats = loss_and_stats(*args, **kw)
        return value, {f"{group}_stats": {f"{group}_{k}": stats[k] for k in keys if k in stats}
                       for group, keys in groups.items() if any(k in stats for k in keys)}

    return loss
