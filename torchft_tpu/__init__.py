"""torchft_tpu: TPU-native per-step fault tolerance for data-parallel training.

A from-scratch JAX/XLA framework with the capabilities of torchft
(reference: /root/reference): lighthouse quorum control plane (C++),
reconfigurable collective communicators, error-swallowing managed allreduce,
two-phase commit, live peer-to-peer checkpoint recovery, and fault-tolerant
DDP / HSDP / LocalSGD / DiLoCo training algorithms.
"""

__version__ = "0.1.0"

# Lazy top-level exports (reference: torchft/__init__.py re-exports the user
# API). Lazy so that `import torchft_tpu` stays light — no jax/native loads
# until a symbol is touched.
_EXPORTS = {
    "Manager": "torchft_tpu.manager",
    "WorldSizeMode": "torchft_tpu.manager",
    "ProcessGroupHost": "torchft_tpu.process_group",
    "ProcessGroupDummy": "torchft_tpu.process_group",
    "ManagedProcessGroup": "torchft_tpu.process_group",
    "ProcessGroupXLA": "torchft_tpu.process_group_xla",
    "DistributedDataParallel": "torchft_tpu.ddp",
    "PureDistributedDataParallel": "torchft_tpu.ddp",
    "BucketPlan": "torchft_tpu.bucketing",
    "BufferPool": "torchft_tpu.bucketing",
    "OptimizerWrapper": "torchft_tpu.optim",
    "LocalSGD": "torchft_tpu.local_sgd",
    "DiLoCo": "torchft_tpu.local_sgd",
    "DistributedSampler": "torchft_tpu.data",
    "StatefulDataIterator": "torchft_tpu.data",
    "HTTPTransport": "torchft_tpu.checkpointing",
    "PGTransport": "torchft_tpu.checkpointing",
    "DurableCheckpointer": "torchft_tpu.checkpointing",
    "LighthouseServer": "torchft_tpu.coordination",
    "LighthouseClient": "torchft_tpu.coordination",
    "ManagerServer": "torchft_tpu.coordination",
    "ManagerClient": "torchft_tpu.coordination",
    "ServeConfig": "torchft_tpu.serving",
    "ServeWorker": "torchft_tpu.serving",
    "SnapshotPublisher": "torchft_tpu.serving",
    "SnapshotRegistry": "torchft_tpu.serving",
}

__all__ = ["__version__", *_EXPORTS]


def __getattr__(name: str):
    if name in _EXPORTS:
        import importlib

        return getattr(importlib.import_module(_EXPORTS[name]), name)
    raise AttributeError(f"module 'torchft_tpu' has no attribute {name!r}")


def __dir__():
    return sorted(__all__)
