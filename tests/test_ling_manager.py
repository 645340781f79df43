"""``ling_debug`` under the Manager: ten committed steps with a heal that
carries the frozen bias."""

import jax

from torchft_tpu.models import CONFIGS
from torchft_tpu.models import ling as L


def test_ten_committed_steps_under_the_manager_with_a_heal_that_carries_the_bias(tmp_path):
    """``ling_debug`` through the launcher, the lighthouse, the Manager and
    the one trainer, two groups: ten committed steps each and none
    discarded, the loss falls, group 1 heals from group 0 in step 1 and ends
    with group 0's ``expert_bias`` bitwise (its own seed's is another) and
    with bitwise-equal parameters; the new counters ride the SUMMARY line."""
    from test_trainer_model_kinds import _checksum, _train

    a, b = sorted(_train("ling_debug", tmp_path, "--steps", "10", groups=2),
                  key=lambda s: s["replica"])
    for s in (a, b):
        assert s["config"] == "ling_debug" and s["committed"] == 10 and s["discarded"] == 0, s
        assert sorted(s["model_stats"]) == [
            "moe_bias_moved_share", "moe_groups_hit_mean", "moe_held_pair_share",
            "moe_load_max_over_mean", "moe_moved_row_share", "moe_overflow_pairs",
            "moe_visited_row_share"]
        assert all(0 < v < 1 for v in s["model_stats"]["moe_visited_row_share"])
        assert all(v == 0 for v in s["model_stats"]["moe_overflow_pairs"])
        assert all(v <= 2 for v in s["model_stats"]["moe_groups_hit_mean"])
        assert all(5.0 < x < 7.0 for x in s["losses"])
    assert a["losses"][-1] < a["losses"][0]
    assert b["healed"] >= 1 and a["healed"] == 0
    source, own = (_checksum(L.ling_init(jax.random.PRNGKey(r), CONFIGS["ling_debug"])[
        "expert_bias"]) for r in (0, 1))
    assert source != own
    assert a["frozen_checksum"] == b["frozen_checksum"] == source
    assert a["param_checksum"] == b["param_checksum"]
