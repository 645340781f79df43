from torchft_tpu.models.kinds import _KINDS, ModelFns, model_fns, split_frozen
from torchft_tpu.models.llama import (
    CONFIGS,
    LlamaConfig,
    llama_forward,
    llama_init,
    llama_loss,
)

__all__ = ["LlamaConfig", "llama_init", "llama_forward", "llama_loss",
           "CONFIGS", "ModelFns", "model_fns", "split_frozen"]


def _register_presets() -> None:
    """``CONFIGS`` is the registry ``--config`` reads: every kind's presets
    stand in it beside the dense ones under their own names. Importing a
    kind's module is what registers the kind (``models/kinds.py``) and its
    presets: a new kind adds its module to this line and nothing else here."""
    from torchft_tpu.models import (brumby, deepseek, jamba, lfm2, ling, mellum,  # noqa: F401
                                    moe, nemotron_h, ouro, solar)

    for _, presets in _KINDS.values():
        for name, cfg in presets.items():
            CONFIGS.setdefault(name, cfg)


_register_presets()
