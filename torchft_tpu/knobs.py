"""Central ``TORCHFT_*`` knob registry: the single source of truth every
env-var contract check hangs off.

Every environment variable the package reads is declared here once, with
its type, default, the doc section that explains it, and the doctor check
(if any) that validates it on a live host. The fleetlint env-contract
checker (``torchft_tpu/analysis/env_contract.py``) cross-checks this
registry three ways:

- a ``TORCHFT_*`` read in code that is **not** registered here is an
  *unregistered read* (new knobs must land with a registration);
- a registered knob that is never read anywhere is a *dead knob*;
- a registered knob whose name does not appear in ``docs/api.md`` is
  *undocumented*, and one with ``doctor=None`` is *un-doctored* (accepted
  ones live in the committed fleetlint baseline with a justification).

Runtime code funnels reads through :func:`env_raw` (or the typed
wrappers) so an unregistered name fails loudly in tests instead of
becoming a silent tribal-knowledge knob.
"""

from __future__ import annotations

import contextlib
import os
import threading
from dataclasses import dataclass
from typing import Callable, Dict, Iterator, Optional, TypeVar

T = TypeVar("T")


@dataclass(frozen=True)
class Knob:
    """One registered environment variable."""

    name: str  # full TORCHFT_* env name
    type: str  # "str" | "int" | "float" | "bool" | "enum(...)"
    default: str  # human-readable default ("" = unset)
    doc: str  # docs anchor, e.g. "api.md#environment-contract"
    doctor: Optional[str]  # doctor check name validating it, or None
    summary: str  # one-line operator-facing description


def _k(
    name: str,
    type: str,
    default: str,
    doc: str,
    doctor: Optional[str],
    summary: str,
) -> Knob:
    return Knob(name, type, default, doc, doctor, summary)


REGISTRY: Dict[str, Knob] = {
    k.name: k
    for k in [
        # ------------------------------------------------- control plane
        _k("TORCHFT_LIGHTHOUSE", "str", "", "api.md#manager", "aggregator",
           "Root lighthouse address (host:port) managers coordinate through."),
        _k("TORCHFT_LIGHTHOUSE_AGGREGATOR", "str", "", "operations.md#running-a-fleet",
           "aggregator",
           "Pod-level lighthouse aggregator address; beats fail over to the root."),
        _k("TORCHFT_MANAGER_PORT", "int", "0", "api.md#manager", "tuning-env",
           "Bind port for the group-leader ManagerServer (0 = ephemeral)."),
        _k("TORCHFT_TIMEOUT_SEC", "float", "60", "api.md#manager", "retry-env",
           "Default control-plane RPC deadline in seconds."),
        _k("TORCHFT_QUORUM_TIMEOUT_SEC", "float", "60", "api.md#manager", "retry-env",
           "Quorum formation deadline; retry backoff budgets are ordered below it."),
        _k("TORCHFT_CONNECT_TIMEOUT_SEC", "float", "10", "api.md#manager", "tuning-env",
           "TCP connect deadline for control-plane clients."),
        _k("TORCHFT_QUORUM_RETRIES", "int", "0", "api.md#manager", "tuning-env",
           "Consecutive quorum failures tolerated before the manager raises."),
        _k("TORCHFT_HEARTBEAT_INTERVAL_MS", "float", "100", "api.md#manager",
           "health-env",
           "Manager heartbeat cadence; health probation windows are sized against it."),
        _k("TORCHFT_HOST", "str", "127.0.0.1", "api.md#process-groups", "tuning-env",
           "Hostname the XLA store/transport advertises (multi-host fleets)."),
        # --------------------------------------------------- data plane
        _k("TORCHFT_BUCKET_CAP_MB", "float", "1024", "performance.md#bucketing", "tuning-env",
           "Allreduce flat-bucket cap in MB (at most this a bucket); 0 disables "
           "bucketing. A host-plane op over device leaves is cut at "
           "bucketing.RUN_BYTES (128 MiB) where the cap is larger."),
        _k("TORCHFT_COMPRESS", "enum(off|fp8|int8)", "off",
           "performance.md#compressed-collectives", "compress-env",
           "Wire codec for streamed buckets, with per-bucket error feedback."),
        _k("TORCHFT_STREAM_CHUNK_BYTES", "int", "1048576", "api.md#checkpointing",
           "tuning-env",
           "Heal/checkpoint transport chunk size in bytes."),
        _k("TORCHFT_USE_BUCKETIZATION", "bool", "0", "performance.md#bucketing", "tuning-env",
           "LocalSGD/DiLoCo fragment bucketization toggle."),
        # -------------------------------------------------- retry plane
        _k("TORCHFT_RETRY_MAX_ATTEMPTS", "int", "3", "operations.md#failure-modes",
           "retry-env", "Control-plane RPC attempts before RetryBudgetExhausted."),
        _k("TORCHFT_RETRY_BASE_S", "float", "0.1", "operations.md#failure-modes",
           "retry-env", "First retry backoff in seconds (doubles per attempt)."),
        _k("TORCHFT_RETRY_MAX_BACKOFF_S", "float", "5", "operations.md#failure-modes",
           "retry-env", "Backoff ceiling; must stay below the quorum timeout."),
        _k("TORCHFT_RETRY_JITTER", "float", "0.5", "operations.md#failure-modes",
           "retry-env", "Backoff jitter fraction decorrelating retry herds."),
        # ------------------------------------------------- health plane
        _k("TORCHFT_HEALTH_MODE", "enum(off|observe|eject)", "observe",
           "operations.md#straggler-management", "health-env",
           "Healthwatch escalation mode."),
        _k("TORCHFT_HEALTH_WINDOW", "int", "32",
           "operations.md#straggler-management", "health-env",
           "Rolling telemetry window per replica."),
        _k("TORCHFT_HEALTH_MIN_SAMPLES", "int", "5",
           "operations.md#straggler-management", "health-env",
           "Warmup samples before a replica is scored."),
        _k("TORCHFT_HEALTH_WARN_Z", "float", "3.0",
           "operations.md#straggler-management", "health-env",
           "Modified z-score that marks a straggler warn."),
        _k("TORCHFT_HEALTH_EJECT_Z", "float", "6.0",
           "operations.md#straggler-management", "health-env",
           "Modified z-score that counts an eject strike."),
        _k("TORCHFT_HEALTH_EJECT_STEPS", "int", "3",
           "operations.md#straggler-management", "health-env",
           "Consecutive strikes before proactive ejection."),
        _k("TORCHFT_HEALTH_PROBATION_MS", "int", "10000",
           "operations.md#straggler-management", "health-env",
           "Probationary readmission window after an eject."),
        _k("TORCHFT_HEALTH_PROBE_OK", "int", "3",
           "operations.md#straggler-management", "health-env",
           "Clean probation samples required for readmission."),
        _k("TORCHFT_HEALTH_REL_FLOOR", "float", "0.05",
           "operations.md#straggler-management", "health-env",
           "Relative slowdown floor below which z-scores never escalate."),
        # ------------------------------------------------ observability
        _k("TORCHFT_TRACE", "bool", "1", "observability.md#span-taxonomy",
           "trace-env", "Span recorder on/off (on by default, <1% overhead)."),
        _k("TORCHFT_TRACE_BUFFER", "int", "65536", "observability.md#span-taxonomy",
           "trace-env", "Span ring capacity (floor 16; overflow is counted)."),
        _k("TORCHFT_TRACE_DIR", "str", "", "observability.md#span-taxonomy",
           "trace-env", "Trace dump directory (empty = beside flight-recorder dumps)."),
        _k("TORCHFT_METRICS_PORT", "int", "", "observability.md#metrics-reference",
           "tuning-env", "Manager-side Prometheus /metrics port (unset = not served)."),
        _k("TORCHFT_METRICS_PER_REPLICA_LIMIT", "int", "64",
           "observability.md#metrics-reference", "tuning-env",
           "Per-replica series cap on the lighthouse /metrics exporter."),
        _k("TORCHFT_FR_BASE_PATH", "str", "", "api.md#observability", "tuning-env",
           "Flight-recorder dump directory (empty = temp dir)."),
        _k("TORCHFT_FR_CAPACITY", "int", "512", "api.md#observability", "tuning-env",
           "Flight-recorder ring capacity in events."),
        _k("TORCHFT_USE_OTEL", "bool", "0", "api.md#observability", "tuning-env",
           "Mirror structured events to an OTLP exporter when available."),
        _k("TORCHFT_OTEL_RESOURCE_ATTRIBUTES_JSON", "str", "", "api.md#observability",
           "tuning-env", "Extra OTLP resource attributes as a JSON object."),
        # ------------------------------------------------ serving plane
        _k("TORCHFT_SERVE_REGISTRY", "str", "", "serving.md#env-contract",
           "serve-env", "Snapshot-registry base URL; empty disables the plane."),
        _k("TORCHFT_SERVE_MAX_LAG", "int", "8", "serving.md#env-contract",
           "serve-env", "Delta-ring depth; workers further behind full-pull."),
        _k("TORCHFT_SERVE_COMPRESS", "enum(off|fp8|int8)", "fp8",
           "serving.md#env-contract", "serve-env",
           "Delta wire codec for published snapshots."),
        _k("TORCHFT_SERVE_POLL_S", "float", "0.05", "serving.md#env-contract",
           "serve-env", "Worker poll interval in seconds."),
        _k("TORCHFT_SERVE_DRAIN_ON", "enum(warn|eject)", "warn",
           "serving.md#env-contract", "serve-env",
           "Health state that drains a source from serve rotation."),
        _k("TORCHFT_SERVE_PORT", "int", "0", "serving.md#env-contract",
           "serve-env", "Inference worker HTTP port (0 = ephemeral)."),
        _k("TORCHFT_SERVE_TIMEOUT_S", "float", "15", "serving.md#env-contract",
           "serve-env", "Per-pull / per-RPC deadline on the serving plane."),
        # --------------------------------------------- redundancy plane
        _k("TORCHFT_REDUNDANCY_K", "int", "0", "operations.md#fast-recovery",
           "redundancy-env", "Erasure data shards per generation; 0 = plane off."),
        _k("TORCHFT_REDUNDANCY_M", "int", "1", "operations.md#fast-recovery",
           "redundancy-env", "Erasure parity shards per generation."),
        _k("TORCHFT_REDUNDANCY_DIRECTORY", "str", "", "operations.md#fast-recovery",
           "redundancy-env", "ShardDirectory base URL (lighthouse --redundancy-directory)."),
        _k("TORCHFT_REDUNDANCY_INTERVAL", "int", "1", "operations.md#fast-recovery",
           "redundancy-env", "Stage shards every N committed generations."),
        _k("TORCHFT_REDUNDANCY_TIMEOUT_S", "float", "15", "operations.md#fast-recovery",
           "redundancy-env", "Per shard-RPC deadline."),
        _k("TORCHFT_REDUNDANCY_RETAIN", "int", "2", "operations.md#fast-recovery",
           "redundancy-env", "Shard generations retained per owner in each store."),
        _k("TORCHFT_POD", "str", "", "operations.md#running-a-fleet", "tuning-env",
           "Placement pod identity (defaults to the aggregator-derived pod)."),
        # ------------------------------------------------------ policy plane
        _k("TORCHFT_POLICY", "enum(off|observe|enforce)", "off",
           "operations.md#adaptive-policies", "policy-env",
           "Adaptive policy engine mode: off = byte-identical legacy"
           " behavior, observe = log would-be actions, enforce = apply."),
        _k("TORCHFT_POLICY_SPEC", "str", "builtin",
           "operations.md#adaptive-policies", "policy-env",
           "PolicySpec source: 'builtin' or a path to a PolicySpec JSON."),
        _k("TORCHFT_POLICY_INTERVAL_S", "float", "5",
           "operations.md#adaptive-policies", "policy-env",
           "Engine evaluation cadence in seconds (fold + rule pass)."),
        _k("TORCHFT_POLICY_WINDOW_S", "float", "300",
           "operations.md#adaptive-policies", "policy-env",
           "Rolling window the fleet signals (MTBF, churn, ...) cover."),
        _k("TORCHFT_POLICY_RING", "int", "4096",
           "operations.md#adaptive-policies", "policy-env",
           "Lighthouse in-memory event-ring capacity feeding the engine."),
        _k("TORCHFT_SYNC_EVERY", "int", "0",
           "operations.md#adaptive-policies", "policy-env",
           "LocalSGD/DiLoCo sync_every override (> 0 wins over the"
           " constructor argument; the policy plane retargets it live)."),
        # ---------------------------------------------------- degrade plane
        _k("TORCHFT_DEGRADE", "enum(off|on)", "off",
           "operations.md#degraded-replicas", "degrade-env",
           "Degrade-in-place: shrink TP/PP onto surviving chips instead of"
           " leaving the quorum when a group member dies."),
        _k("TORCHFT_DEGRADE_MIN_DEGREE", "int", "1",
           "operations.md#degraded-replicas", "degrade-env",
           "Smallest surviving group degree worth resharding onto; below it"
           " the replica falls back to the classic leave-heal-rejoin path."),
        _k("TORCHFT_DEGRADE_RESTORE", "enum(auto|manual)", "auto",
           "operations.md#degraded-replicas", "degrade-env",
           "Restore policy: auto re-promotes when a repaired chip reports in;"
           " manual waits for an operator restore_full_degree() call."),
        # -------------------------------------------------- device plane
        _k("TORCHFT_XLA_HEARTBEAT_SEC", "float", "10", "api.md#process-groups", "tuning-env",
           "XLA process-group peer heartbeat timeout."),
        _k("TORCHFT_WATCHDOG_TIMEOUT_SEC", "float", "30", "api.md#futures", "tuning-env",
           "Future-watchdog deadline that converts a wedged wait into an error."),
        _k("TORCHFT_TPU_ATTENTION", "enum(auto|splash|flash|reference)", "auto",
           "api.md#models", None, "Attention kernel selector."),
        _k("TORCHFT_TPU_SPLASH_BLOCK", "int", "", "api.md#models", None,
           "Splash-attention tile override (both dimensions)."),
        _k("TORCHFT_TPU_SPLASH_BLOCK_KV", "int", "", "api.md#models", None,
           "Splash-attention kv-side tile override."),
        _k("TORCHFT_TPU_SCAN_UNROLL", "int", "1", "api.md#models", None,
           "Layer-scan unroll factor (benchmarking)."),
    ]
}


def is_registered(name: str) -> bool:
    return name in REGISTRY


def all_knobs() -> Dict[str, Knob]:
    """A copy of the registry (name -> Knob)."""
    return dict(REGISTRY)


# ---------------------------------------------------------------------------
# Override layer (policy plane). The adaptive policy engine retargets knobs
# at the Manager's quorum safe point by installing string values here;
# every read funnelled through env_raw sees an override before the process
# environment, so the central registry stays the single source of truth
# fleetlint's env-contract checks hang off — an override can only name a
# registered knob. Overrides are process-local and never mutate os.environ
# (a policy rollback must not leave residue in the environment).
_overrides: Dict[str, str] = {}
_overrides_mu = threading.Lock()


def set_override(name: str, value: Optional[str]) -> None:
    """Install (or, with ``None``, clear) one override. The name must be
    registered; values are strings exactly as an env var would carry."""
    if name not in REGISTRY:
        raise KeyError(
            f"{name} is not in the TORCHFT knob registry — overrides can "
            "only retarget registered knobs"
        )
    with _overrides_mu:
        if value is None:
            _overrides.pop(name, None)
        else:
            _overrides[name] = str(value)


def get_overrides() -> Dict[str, str]:
    """Snapshot of the active override set (name -> value)."""
    with _overrides_mu:
        return dict(_overrides)


def clear_overrides() -> None:
    """Drop every active override (the policy kill switch)."""
    with _overrides_mu:
        _overrides.clear()


@contextlib.contextmanager
def override_scope(values: Dict[str, str]) -> Iterator[None]:
    """Scoped knob overrides: install ``values`` on entry, restore the
    previous override state on exit. Nesting composes (inner scopes win
    while active). Unregistered names raise before anything is changed."""
    for name in values:
        if name not in REGISTRY:
            raise KeyError(
                f"{name} is not in the TORCHFT knob registry — overrides "
                "can only retarget registered knobs"
            )
    with _overrides_mu:
        saved = dict(_overrides)
        _overrides.update({k: str(v) for k, v in values.items()})
    try:
        yield
    finally:
        with _overrides_mu:
            _overrides.clear()
            _overrides.update(saved)


def env_raw(name: str, default: Optional[str] = None) -> Optional[str]:
    """``os.environ.get`` gated on registration: reading a knob that was
    never declared is a contract bug, surfaced here instead of shipping as
    an undocumented env var. Active policy overrides (``override_scope``)
    take precedence over the process environment."""
    if name not in REGISTRY:
        raise KeyError(
            f"{name} is not in the TORCHFT knob registry "
            "(torchft_tpu/knobs.py) — register it with a type, default, "
            "doc anchor, and doctor coverage before reading it"
        )
    with _overrides_mu:
        if name in _overrides:
            return _overrides[name]
    return os.environ.get(name, default)


def _typed(name: str, default: T, cast: Callable[[str], T]) -> T:
    raw = env_raw(name)
    if raw is None or raw == "":
        return default
    return cast(raw)


def env_str(name: str, default: str = "") -> str:
    return _typed(name, default, str)


def env_int(name: str, default: int = 0) -> int:
    return _typed(name, default, int)


def env_float(name: str, default: float = 0.0) -> float:
    return _typed(name, default, float)


def env_bool(name: str, default: bool = False) -> bool:
    raw = env_raw(name)
    if raw is None or raw.strip() == "":
        return default
    return raw.strip().lower() not in ("0", "false", "no", "off")
