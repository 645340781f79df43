"""Adapter ``llama``: what is ``models/llama.py``'s own, and nothing else.
A configuration file names its adapter under the key ``adapter``; a file
without the key gets this one, so the two dense configurations stand as they
were. An adapter is everything a job kind, the worker and a reducer need to
know of one architecture:

    config(cfg)              the file (Hugging Face keys) as the program's
                             config object; refuses a key it cannot express
    register(cfg)            makes the configuration known to the trainer
                             under a name; returns the script the worker runs
                             as ``__main__`` and the arguments that select it
    program()                the program's (init, loss, forward) for the fused
                             bare step and the system's side of the check
    reference                the plain reference: a module with ``forward``,
                             ``loss``, ``check_sample``, ``grad_answers`` and a
                             script entry; its file is hashed into the key of
                             the cached answers
    GRAD_LEAVES              the gradient leaves the check samples, where the
                             tree has other names than the traffic file's
                             (None: the traffic file's)
    train_flops_per_token(cfg, seq), num_params(cfg)
    KERNEL_COSTS[kernel](cfg, batch, seq, passes) -> {"flops", "bytes"} of one
                             call; layers_with(cfg, kernel): the layers of a
                             step that make such calls

The arithmetic stays where it stood (``worker.llama_config``,
chipbench/reference.py, chipbench/flops.py): this file only names it.
"""

from chipbench import flops, reference  # noqa: F401  (reference: see above)
from chipbench.worker import TRAINER, llama_config

GRAD_LEAVES = None

# the keys ``llama_config`` reads or tests, and those that say what the file
# is; any other key is a property of the model this code would drop in silence
_EXPRESSED = {
    "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "intermediate_size",
    "max_position_embeddings", "rope_theta", "rms_norm_eps", "hidden_act",
    "sliding_window", "rope_scaling", "tie_word_embeddings", "bias"}
_DESCRIBES = {
    "name", "source", "adapter", "architectures", "model_type", "published",
    "reduced", "assumed", "recipe", "cut", "stands_for"}


def config(cfg: dict):
    unknown = sorted(set(cfg) - _EXPRESSED - _DESCRIBES)
    if unknown:
        raise ValueError(
            "adapter 'llama' cannot express key " + ", ".join(map(repr, unknown))
            + ": models/llama.py is a dense decoder; a model with other parts "
            "brings its own chipbench/adapters/<name>.py")
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError(f"key 'hidden_act': models/llama.py is SwiGLU, not "
                         f"{cfg['hidden_act']!r}")
    return llama_config(cfg)


def register(cfg: dict) -> "tuple[str, list[str]]":
    from torchft_tpu.models.llama import CONFIGS

    CONFIGS[cfg["name"]] = config(cfg)
    return TRAINER, ["--config", cfg["name"]]


def program():
    from torchft_tpu.models.llama import llama_forward, llama_init, llama_loss

    return llama_init, llama_loss, llama_forward


train_flops_per_token = flops.train_flops_per_token
num_params = flops.num_params
KERNEL_COSTS = {"attention": flops.attention_kernel_cost}


def layers_with(cfg: dict, kernel: str) -> int:
    return cfg["num_hidden_layers"]
