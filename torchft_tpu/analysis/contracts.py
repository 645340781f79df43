"""Declared observability contract: the single list of every key that is
allowed to flow through ``Manager.timings()`` and the manager-side
Prometheus exporter.

The counter-contract checker
(``torchft_tpu/analysis/counter_contract.py``) statically extracts the
keys ``manager.py`` / ``redundancy.py`` actually emit and diffs both
directions: an emitted key missing here is *undeclared* (new telemetry
must land with a declaration and a docs/observability.md row), and a key
declared here that no longer appears in code is a *dead declaration*
(emission was removed without updating the contract). Every declared key
must also be mentioned in ``docs/observability.md``.
"""

from __future__ import annotations

from typing import Dict

# key -> one-line meaning (kept short: docs/observability.md is the
# operator-facing reference; this is the machine-checked index)
DECLARED_TIMINGS: Dict[str, str] = {
    # quorum / reconfigure phases
    "quorum_overlap_s": "control-plane time on the quorum thread",
    "configure_prepare_s": "overlappable half of the PG reconfigure",
    "configure_commit_s": "serializing half of the PG reconfigure",
    "should_commit_rpc_s": "commit-vote RPC wall clock",
    "bookkeeping_s": "residual commit-path bookkeeping",
    # heal plane
    "heal_send_s": "serving a live checkpoint to a peer",
    "heal_recv_s": "fetching + applying a live checkpoint",
    "heal_fetch_s": "socket -> recv buffers, summed over fetch threads",
    "heal_place_s": "placing healed leaves, summed over fetch threads",
    "heal_apply_s": "load_state_dict of the healed state (main thread)",
    "first_step_compile_s": "backend compiles (cache lookups included) up to the first commit",
    "heal_chunks": "chunks in the last heal stream",
    "heal_mb_per_s": "last heal stream throughput",
    "heal_attempts": "cumulative heal tries (incl. same-source retries)",
    "heal_failovers": "cumulative mid-heal source switches",
    "chunk_crc_failures": "chunks refetched after integrity mismatch",
    # allreduce pipeline
    "allreduce_s": "submission→resolve wall clock of the last collective",
    "allreduce_pack_s": "summed per-bucket pack stage",
    "allreduce_wire_s": "summed per-bucket wire stage",
    "allreduce_unpack_s": "summed per-bucket unpack stage",
    "allreduce_buckets": "buckets in the last streamed allreduce",
    "overlap_efficiency": "fraction of wire time hidden behind other stages",
    "stage_pool_hit_share": (
        "device buckets of the last streamed allreduce fetched into a "
        "recycled pool buffer, over all of them"
    ),
    "wire_passthrough_share": (
        "device buckets of the last streamed allreduce whose collective "
        "resolved to the staging buffer it was given, over all of them"
    ),
    "ring_lanes": (
        "fewest connections to a ring neighbour that any host ring of the "
        "step rode: process_group._RING_LANES, or 1 under its floor, at a "
        "world of one and where the native fold is missing"
    ),
    # the plain host ring's own account, summed over the step's runs
    # (bucketing.RING_KEYS; the five after the first are the lanes' means)
    "ring_entry_wait_s": "run start on the dispatch thread to first peer byte",
    "ring_recv_wait_s": "receivers blocked for a frame's header after that",
    "ring_recv_s": "receivers taking payloads off their sockets",
    "ring_fold_s": "folds adding a received frame into its segment",
    "ring_send_s": "writers inside send_raw (back-pressure included)",
    "ring_handoff_s": "a lane's threads waiting on each other past the frame",
    "collective_reroute": "cumulative mid-collective link reroutes",
    # control plane (two-level)
    "via_aggregator": "1 when control RPCs ride the pod aggregator",
    "aggregator_failovers": "cumulative aggregator→root failovers",
    "rpc_retries": "cumulative retried control-plane RPCs",
    # health plane
    "health_state": "lighthouse health state code for this replica",
    "straggler_score": "quorum-relative modified z-score",
    "ejections": "cumulative proactive ejections of this replica",
    "readmissions": "cumulative probationary readmissions",
    # policy plane (adaptive FT control, quorum-safe-point application)
    "policy_seq": "latest policy frame sequence seen at a safe point",
    "policy_applies": "frames whose overrides were enforced live",
    "policy_intents": "frames recorded in observe mode (no knob touched)",
    # degrade plane (in-place TP/PP shrink after an intra-group chip loss)
    "degraded_reshard_s": "last in-place k→k-1 reshard wall clock",
    "degrade_events": "cumulative in-place degrades of this replica",
    "restored_events": "cumulative full-degree restores after a degrade",
    # observability honesty counters
    "dropped_events": "telemetry events shed by the bounded drain",
    "trace_dropped": "spans overwritten in the trace ring",
    # serving plane (commit-path publisher)
    "serve_publish_s": "commit-path snapshot handoff wall clock",
    "serve_published_total": "snapshots handed to the publisher",
    "serve_publish_errors_total": "failed snapshot handoffs",
    # redundancy plane — manager side
    "shard_stage_hot_s": "hot-path cost of handing state to the stager",
    "standby_skipped": "standby snapshots refused while mid-heal",
    "reconstructs": "heals satisfied by parallel shard reconstruct",
    "reconstruct_failures": "reconstruct attempts that fell back to pull",
    "reconstruct_s": "last parallel reconstruct wall clock",
    "reconstruct_mb_per_s": "last parallel reconstruct throughput",
    "shard_corrupt": "shards that failed crc32 on the GET path",
    "shard_fetch_failed": "shard GETs that failed outright",
    "spare_promote_step": "step at which this spare was promoted",
    # redundancy plane — stager/spare bridge (_on_metric)
    "shard_stage_s": "staging wall clock off the hot path",
    "shard_stage_snapshot_s": "hot-path state snapshot cost",
    "shard_encode_s": "GF(256) parity encode wall clock",
    "shard_stage_bytes": "bytes in the last staged state blob",
    "shards_staged": "cumulative shards PUT to peer stores",
    "shard_stage_skipped": "stagings skipped by the interval knob",
    "shard_stage_dropped": "stagings dropped by newest-wins queueing",
    "shard_stage_failed": "stagings that failed end to end",
    "shard_put_failed": "individual shard PUTs that failed",
    "shard_announce_rejected": "directory announces rejected as stale",
    "spare_prefetch_s": "hot-spare decode-ahead wall clock",
    "spare_prefetch_steps": "generations prefetched by the hot spare",
}

# explicit Prometheus series registered on the manager exporter (beyond
# the mechanical torchft_manager_<timings-key> projections)
DECLARED_SERIES: Dict[str, str] = {
    "torchft_manager_step": "current manager step",
    "torchft_manager_quorum_id": "current PG generation",
    "torchft_manager_trace_spans_total": "spans recorded into the ring",
    "torchft_manager_clock_skew_ms": "heartbeat-derived skew estimate",
    "torchft_manager_clock_skew_rtt_ms": "RTT of the best skew sample",
}
