"""`python bench.py --smoke` is the CI gate for the overlapped-quorum
plumbing: a tiny virtual-device FT row must produce the per-phase timing
keys end to end (async quorum overlap, prepare/commit split, chunked
heal). `--ft-overhead --smoke` is the gate for the steady-state overhead
harness: the real example trainer under a live Manager must emit
ft_overhead_pct plus the per-phase cost splits. `--healthwatch --smoke` is
the gate for the health telemetry plane: the per-step publish+fold cost
must stay under 1% of the managed step and /health must answer every
poll made while the trainer is live. `--tracing --smoke` is the gate for
the fleet tracing plane: span recording must stay under 1% of the
managed step and the Prometheus /metrics endpoint must answer every
scrape made while the trainer is live. `--fleet --smoke` is the gate
for the fleet-scale control plane: a simulated fleet (flat and two-level)
must converge its quorum rounds and the aggregator tier must show a real
fan-in reduction at the root. `--recovery --smoke` is the gate for the
redundancy plane: the parallel erasure reconstruct must beat the
single-source heal wire and the commit-path cost of shard staging must
stay a small fraction of the managed step. `--degrade --smoke` is the
gate for the degrade-in-place plane: killing one chip of a 4-chip
replica group must reshard in place faster than the classic
leave-heal-rejoin cycle with the quorum never shrinking and the
shrunken layout bitwise-equal. `--policy --smoke` is the gate for the
adaptive policy plane: the engine's 1000-replica fold must amortize to
<0.5% of a managed step, the offline replay must rank >=2 candidate
specs against the committed fixture, and a versioned frame must reach a
live manager's quorum safe point over the existing wire."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_bench(*argv):
    proc = subprocess.run(
        [sys.executable, "bench.py", *argv],
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, (
        f"bench {' '.join(argv)} failed\nstdout:\n{proc.stdout[-2000:]}"
        f"\nstderr:\n{proc.stderr[-2000:]}"
    )
    lines = [l for l in proc.stdout.splitlines() if l.startswith("{")]
    assert lines, f"no JSON record in smoke output:\n{proc.stdout[-2000:]}"
    return json.loads(lines[-1])


def test_bench_smoke_emits_overlap_metrics():
    rec = _run_bench("--smoke")
    # the smoke run itself asserts these are present and sane; re-check the
    # load-bearing ones here so a silently-weakened smoke() still fails CI
    assert rec["ft_virtual_quorum_overlap_s"] > 0
    assert rec["ft_virtual_configure_prepare_s"] is not None
    assert rec["ft_virtual_configure_commit_s"] is not None
    assert rec["ft_virtual_heal_chunks"] >= 1
    assert rec["ft_virtual_heal_mb_per_s"] > 0
    assert rec["ft_virtual_recovery_s"] > 0


def test_bench_ft_overhead_smoke_emits_cost_splits():
    rec = _run_bench("--ft-overhead", "--smoke")
    assert rec["ft_overhead_pct"] is not None
    assert rec["bare_step_s"] > 0
    assert rec["ft_step_s"] > 0
    # the per-phase splits prove Manager.timings() measured the hot loop,
    # not just that the harness ran
    assert rec["allreduce_s"] > 0
    assert rec["should_commit_rpc_s"] > 0
    assert rec["bookkeeping_s"] >= 0


def test_bench_healthwatch_smoke_holds_cost_and_serves_health():
    rec = _run_bench("--healthwatch", "--smoke")
    # the smoke run itself gates these; re-check the load-bearing ones so a
    # silently-weakened healthwatch() still fails CI
    assert rec["healthwatch_overhead_pct"] < 1.0
    assert rec["healthwatch_publish_s"] > 0
    assert rec["health_polls_ok"] > 0
    assert rec["health_polls_failed"] == 0
    assert rec["health_replicas_tracked"] >= 1
    assert rec["health_mode"] == "observe"


def test_bench_tracing_smoke_holds_cost_and_serves_metrics():
    rec = _run_bench("--tracing", "--smoke")
    # the smoke run itself gates these; re-check the load-bearing ones so
    # a silently-weakened tracing() still fails CI
    assert rec["tracing_overhead_pct"] < 1.0
    assert rec["tracing_span_cost_us"] > 0
    # the count is capped beside the share: a longer step must not hide it
    assert 0 < rec["tracing_spans_per_step"] < 24
    # the hot loop's spans reached the ring with the taxonomy's categories
    assert {"quorum", "commit"} <= set(rec["trace_categories"])
    assert rec["trace_merged_events"] > 0
    # /metrics answered the whole smoke scrape budget under load
    assert rec["metrics_scrapes_ok"] >= 300
    assert rec["metrics_scrapes_failed"] == 0
    assert rec["metrics_series"] > 0


def test_bench_compressed_allreduce_smoke_emits_per_mode_splits():
    rec = _run_bench("--compressed-allreduce", "--smoke")
    # every compress mode ran the streamed multi-bucket path and its
    # stage splits + effective bandwidth survived to the JSON record
    for mode in ("off", "fp8", "int8"):
        m = rec["modes"][mode]
        assert m["step_s"] > 0, mode
        assert m["buckets"] > 1, mode
        assert m["wire_s"] > 0, mode
        assert m["pack_s"] >= 0 and m["unpack_s"] >= 0, mode
        assert m["effective_wire_mb_s"] > 0, mode
    # the ratio itself is host/noise-dependent (smoke payloads are tiny)
    # so only its presence is gated here; the >=2x claim is the committed
    # full-size BENCH_COMPRESS.json's job
    assert rec["bandwidth_ratio_fp8"] is not None
    assert rec["bandwidth_ratio_int8"] is not None


def test_bench_fleet_smoke_holds_fanin_and_convergence():
    rec = _run_bench("--fleet", "--smoke")
    # the smoke run itself gates these; re-check the load-bearing ones so a
    # silently-weakened fleet() still fails CI
    assert rec["fleet_fanin_ratio_at_max"] >= 2.0
    assert rec["fleet_all_converged"] is True
    assert rec["fleet_two_level_convergence_ms_at_max"] > 0
    assert rec["fleet_flat_fanin_bytes_per_tick_at_max"] > 0
    assert rec["fleet_two_level_fanin_bytes_per_tick_at_max"] > 0


def test_bench_recovery_smoke_beats_single_source_and_stays_cheap():
    rec = _run_bench("--recovery", "--smoke")
    # the smoke run itself gates these (>=1.5x parallel speedup, <5%
    # staging overhead, stager kept up); re-check the load-bearing ones
    # here so a silently-weakened recovery() still fails CI
    assert rec["recovery_reconstruct_speedup_x"] >= 1.5
    assert rec["recovery_single_source_s_at_max"] > 0
    assert rec["recovery_parallel_s_at_max"] > 0
    assert rec["staging_overhead_pct"] < 5.0
    assert rec["staging_kept_up"] is True
    # the curve rows must carry the bitwise-verified round-trip evidence
    for row in rec["recovery_curve"]:
        assert row["shards_ok_parallel"] >= rec["recovery_k"]
        assert row["shards_ok_single"] == 1
        assert row["speedup_x"] > 0


def test_bench_degrade_smoke_beats_rejoin_and_keeps_quorum():
    rec = _run_bench("--degrade", "--smoke")
    # the smoke run itself gates these (>=1.5x over leave-heal-rejoin,
    # quorum never shrank, bitwise reshard); re-check the load-bearing
    # ones here so a silently-weakened degrade() still fails CI
    assert rec["degrade_speedup_x"] >= 1.5
    assert rec["degrade_in_place_s_at_max"] > 0
    assert rec["degrade_classic_rejoin_s_at_max"] > 0
    assert rec["degrade_quorum_never_shrank"] is True
    assert rec["degrade_bitwise_ok"] is True
    for row in rec["degrade_curve"]:
        # exactly one chip lost: the gather-free path sourced 1/degree of
        # the state off the wire and the group landed one degree down
        assert row["reshard_mode"] == "peer"
        assert row["group_degree_after"] == row["degree"] - 1
        assert 0 < row["reshard_bytes_sourced"] < row["reshard_bytes_moved"]


def test_bench_policy_smoke_stays_cheap_and_ranks_candidates():
    rec = _run_bench("--policy", "--smoke")
    # the smoke run itself gates these (<0.5% fold duty cycle, >=2-way
    # replay ranking, a frame at the safe point); re-check the
    # load-bearing ones here so a silently-weakened policy() still fails
    assert rec["policy_fold_duty_cycle_pct"] < 0.5
    assert rec["policy_fold_eval_ms"] > 0
    assert rec["replay_events_per_s"] >= 1000
    assert len(rec["replay_ranking"]) >= 2
    assert rec["replay_winner"] == rec["replay_ranking"][0]["policy"]
    # the zero-new-RPC piggyback delivered a versioned frame to a live
    # manager's quorum safe point in observe mode
    assert rec["policy_intents"] >= 1
    assert rec["fixture_replicas"] == 1000


def test_bench_serving_smoke_sustains_traffic_through_kill():
    rec = _run_bench("--serving", "--smoke")
    # the smoke run itself gates these (zero failed requests through the
    # mid-traffic kill, bitwise convergence, delta savings); re-check the
    # load-bearing ones here so a silently-weakened serving() still fails
    assert rec["serving_failed_requests"] == 0
    assert rec["serving_requests_ok"] > 0
    assert rec["serving_converged"] is True
    assert rec["serving_bitwise_equal"] is True
    assert rec["serving_delta_savings_x"] > 1.0
    assert rec["serving_p99_ms"] > 0
    assert all(v > 0 for v in rec["serving_rps_by_workers"].values())
    assert rec["serving_lag_p99_steps"] >= 0
