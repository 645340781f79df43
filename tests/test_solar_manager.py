"""``solar_debug`` under the Manager: committed steps with a heal that
carries the frozen bias."""

import jax

from torchft_tpu.models import CONFIGS
from torchft_tpu.models import solar as M


def test_committed_steps_under_the_manager_with_a_heal_that_carries_the_bias(tmp_path):
    """``solar_debug`` through the launcher, the lighthouse, the Manager and
    the one trainer, two groups: six committed steps each and none
    discarded, the loss falls, group 1 heals from group 0 in step 1 and ends
    with group 0's ``expert_bias`` bitwise (its own seed's is another: the
    frozen leaf stays out of the gradient program and the allreduce, the
    checksum holds it) and with bitwise-equal parameters; the expert block's
    and the KDA layers' counters ride the SUMMARY line."""
    from test_trainer_model_kinds import _checksum, _train

    a, b = sorted(_train("solar_debug", tmp_path, "--steps", "6", groups=2),
                  key=lambda s: s["replica"])
    for s in (a, b):
        assert s["config"] == "solar_debug" and s["committed"] == 6 and s["discarded"] == 0, s
        assert sorted(s["model_stats"]) == [
            "kda_beta_over_one_share", "kda_decay_past_bound_share", "moe_bias_moved_share",
            "moe_held_pair_share", "moe_load_max_over_mean", "moe_moved_row_share",
            "moe_overflow_pairs", "moe_visited_row_share"]
        assert all(v == 0 for v in s["model_stats"]["moe_overflow_pairs"])
        assert all(0.3 < v < 0.7 for v in s["model_stats"]["kda_beta_over_one_share"])
        assert all(0 <= v < 0.05 for v in s["model_stats"]["kda_decay_past_bound_share"])
        assert all(5.0 < x < 7.0 for x in s["losses"])
    assert a["losses"][-1] < a["losses"][0]
    assert b["healed"] >= 1 and a["healed"] == 0
    source, own = (_checksum(M.solar_init(jax.random.PRNGKey(r), CONFIGS["solar_debug"])[
        "expert_bias"]) for r in (0, 1))
    assert source != own
    assert a["frozen_checksum"] == b["frozen_checksum"] == source
    assert a["param_checksum"] == b["param_checksum"]
