from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

from torchft_tpu.models.llama import (
    CONFIGS,
    LlamaConfig,
    llama_forward,
    llama_init,
    llama_loss,
)

__all__ = ["LlamaConfig", "llama_init", "llama_forward", "llama_loss",
           "CONFIGS", "ModelFns", "model_fns", "split_frozen"]


class ModelFns(NamedTuple):
    """What :func:`model_fns` hands out for one kind of configuration."""

    init: Callable[..., Any]
    loss: Callable[..., Any]
    param_specs: Callable[..., Any]
    # (cfg, attention_fn) -> models.staged.Stages, or None for a kind whose
    # gradient is one program (staged_value_and_grad's degenerate chain)
    stages: Optional[Callable[..., Any]]
    # top-level keys of the parameter tree that are STATE and not parameters:
    # the loss reads them, a state dict, a heal and a checksum hold them, and
    # no gradient, allreduce, optimizer update or weight decay touches them
    frozen: Tuple[str, ...] = ()


def split_frozen(params: Dict[str, Any], frozen: Tuple[str, ...]
                 ) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """``params`` as (the trainable leaves, the ``frozen`` ones that are
    there): ``{**trainable, **held}`` is ``params`` again."""
    return ({k: v for k, v in params.items() if k not in frozen},
            {k: params[k] for k in frozen if k in params})


def model_fns(cfg: LlamaConfig) -> ModelFns:
    """A configuration object's ``(init, loss, param_specs, stages,
    frozen)``, by its kind: the one place a trainer learns which model it
    runs.

    ``init(key, cfg)`` -> parameter pytree; ``param_specs(cfg)`` -> its
    PartitionSpecs; ``loss(params, tokens, targets, cfg, attention_fn=,
    remat=)`` -> ``(loss, stats)`` for ``value_and_grad(has_aux=True)``,
    where ``stats`` maps the name of a trace instant to the device scalars a
    training loop fetches beside the loss ({} for a dense model);
    ``stages(cfg, attention_fn)`` -> the same loss (at ``remat="full"``) as
    the stage functions ``models.staged.staged_value_and_grad`` composes into
    a chain of programs, with the same ``stats``; ``frozen``: see
    :class:`ModelFns`."""
    from torchft_tpu.models.jamba import (
        JambaConfig, jamba_init, jamba_loss_and_stats, jamba_param_specs)
    from torchft_tpu.models.lfm2 import (
        LFM2_FROZEN, Lfm2Config, lfm2_init, lfm2_loss_and_stats, lfm2_param_specs)
    from torchft_tpu.models.ling import (
        LING_FROZEN, LingConfig, ling_init, ling_loss_and_stats, ling_param_specs)
    from torchft_tpu.models.llama import llama_stages
    from torchft_tpu.models.mellum import (
        MellumConfig, mellum_init, mellum_loss_and_stats, mellum_param_specs)
    from torchft_tpu.models.moe import (
        MoEConfig, moe_init, moe_loss_and_stats, moe_param_specs, moe_stages)
    from torchft_tpu.parallel.mesh import llama_param_specs

    if isinstance(cfg, LingConfig):  # before MoEConfig: it is one
        def loss(*args: Any, **kw: Any) -> Tuple[Any, Dict[str, Any]]:
            value, stats = ling_loss_and_stats(*args, **kw)
            return value, {"moe_stats": {"moe_" + k: stats[k] for k in (
                "load_max_over_mean", "bias_moved_share", "held_pair_share",
                "overflow_pairs", "groups_hit_mean") if k in stats}}

        return ModelFns(ling_init, loss, ling_param_specs, None, LING_FROZEN)

    if isinstance(cfg, MellumConfig):  # before MoEConfig: it is one
        def loss(*args: Any, **kw: Any) -> Tuple[Any, Dict[str, Any]]:
            value, stats = mellum_loss_and_stats(*args, **kw)
            return value, {
                "moe_stats": {"moe_" + k: stats[k] for k in (
                    "load_max_over_mean", "held_pair_share", "overflow_pairs")
                    if k in stats},
                "attn_stats": {"attn_" + k: stats[k] for k in (
                    "window_layers", "full_layers", "window_block_share")}}

        return ModelFns(mellum_init, loss, mellum_param_specs, None)

    if isinstance(cfg, Lfm2Config):  # before MoEConfig: it is one
        def loss(*args: Any, **kw: Any) -> Tuple[Any, Dict[str, Any]]:
            value, stats = lfm2_loss_and_stats(*args, **kw)
            return value, {"moe_stats": {
                "moe_" + k: stats[k] for k in ("load_max_over_mean", "bias_moved_share")
                if k in stats}}

        return ModelFns(lfm2_init, loss, lfm2_param_specs, None, LFM2_FROZEN)

    if isinstance(cfg, JambaConfig):
        def loss(*args: Any, **kw: Any) -> Tuple[Any, Dict[str, Any]]:
            value, stats = jamba_loss_and_stats(*args, **kw)
            return value, {"ssm_stats": stats}

        return ModelFns(jamba_init, loss, jamba_param_specs, None)

    if isinstance(cfg, MoEConfig):
        def named(stats: Dict[str, Any]) -> Dict[str, Any]:
            return {"moe_stats": {
                "moe_load_max_over_mean": stats["load_max_over_mean"],
                "moe_aux_loss": stats["aux_loss"]}}

        def loss(*args: Any, **kw: Any) -> Tuple[Any, Dict[str, Any]]:
            value, stats = moe_loss_and_stats(*args, **kw)
            return value, named(stats)

        def stages(*args: Any, **kw: Any) -> Any:
            s = moe_stages(*args, **kw)

            def head(*a: Any) -> Tuple[Any, Dict[str, Any]]:
                value, stats = s.head(*a)
                return value, named(stats)

            return s._replace(head=head)

        return ModelFns(moe_init, loss, moe_param_specs, stages)

    def loss(*args: Any, **kw: Any) -> Tuple[Any, Dict[str, Any]]:
        return llama_loss(*args, **kw), {}

    return ModelFns(llama_init, loss, llama_param_specs, llama_stages)


def _register_presets() -> None:
    """``CONFIGS`` is the registry ``--config`` reads: the MoE and the
    hybrid presets stand in it beside the dense ones (an MoEConfig and a
    JambaConfig are LlamaConfigs, an Lfm2Config, a LingConfig and a MellumConfig
    MoEConfigs) under their own
    names; ``debug`` is taken, so the MoE one is ``moe_debug``."""
    from torchft_tpu.models.jamba import JAMBA_CONFIGS
    from torchft_tpu.models.lfm2 import LFM2_CONFIGS
    from torchft_tpu.models.ling import LING_CONFIGS
    from torchft_tpu.models.mellum import MELLUM_CONFIGS
    from torchft_tpu.models.moe import MOE_CONFIGS

    for name, cfg in MOE_CONFIGS.items():
        CONFIGS.setdefault("moe_debug" if name == "debug" else name, cfg)
    for name, cfg in {**JAMBA_CONFIGS, **LFM2_CONFIGS, **LING_CONFIGS,
                      **MELLUM_CONFIGS}.items():
        CONFIGS.setdefault(name, cfg)


_register_presets()
