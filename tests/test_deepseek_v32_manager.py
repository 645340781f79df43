"""``dsv32_debug`` (DeepSeek sparse attention's warm-up stage: a frozen
trunk, the indexers trained) under the Manager: committed steps in two
groups with a heal that carries EVERY leaf, an allreduce that carries the
indexers' gradients alone, and checksums that agree."""

import jax

from torchft_tpu.models import CONFIGS, split_frozen
from torchft_tpu.models import deepseek as M


def test_committed_steps_under_the_manager_with_a_heal_that_carries_the_trunk(tmp_path):
    """``dsv32_debug`` through the launcher, the lighthouse, the Manager and
    the one trainer, two groups: six committed steps each and none
    discarded, the loss (the layers' KL, no cross-entropy) falls; group 1
    heals from group 0 and ends with group 0's FROZEN leaves bitwise (its
    own seed's trunk is another) and with bitwise-equal parameters; the
    gradient goes out as ONE allreduce of the indexers' leaves (8,544
    parameters of 188,808: the bucket plan's bytes say so); the stage's
    counters ride the SUMMARY line."""
    from test_trainer_model_kinds import _checksum, _train

    a, b = sorted(_train("dsv32_debug", tmp_path, "--steps", "6", "--lr", "1e-3", groups=2),
                  key=lambda s: s["replica"])
    cfg = CONFIGS["dsv32_debug"]
    for s in (a, b):
        assert s["config"] == "dsv32_debug" and s["committed"] == 6 and s["discarded"] == 0, s
        assert {"dsa_kl_first", "dsa_kl_last", "dsa_topk_mass", "frozen_param_share",
                "moe_overflow_pairs", "moe_bias_moved_share"} <= set(s["model_stats"])
        assert all(v == 0 for v in s["model_stats"]["moe_overflow_pairs"])
        assert all(abs(v - (1 - cfg.num_trainable() / cfg.num_params())) < 1e-6
                   for v in s["model_stats"]["frozen_param_share"])
        assert all(0.0 < v <= 1.0 for v in s["model_stats"]["dsa_topk_mass"])
        assert all(0.3 < x < 3.0 for x in s["losses"])  # three layers' KL, no ln(vocab)
        assert s["timings"]["allreduce_ops"] == 1
    assert a["losses"][-1] < a["losses"][0]
    assert b["healed"] >= 1 and a["healed"] == 0
    source, own = (_checksum(split_frozen(M.deepseek_init(jax.random.PRNGKey(r), cfg),
                                          M.frozen_keys(cfg))[1]) for r in (0, 1))
    assert source != own
    assert a["frozen_checksum"] == b["frozen_checksum"] == source
    assert a["param_checksum"] == b["param_checksum"]
