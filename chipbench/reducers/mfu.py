"""Model FLOP/s utilization in percent: required FLOPs per token (attention
included, recomputation not; the configuration's adapter names the function:
chipbench/flops.py for ``llama``) x the tokens/s/chip of this run (``of``: the
end-to-end metric that holds it) over the chip's bf16 peak
(chipbench/peaks.json)."""

from chipbench import flops


def reduce(obs, cell, of="tok_s_chip"):
    tps = obs["e2e"].get(of)
    if tps is None:
        return None
    need = cell.adapter().train_flops_per_token(
        cell.config, cell.config["recipe"]["seq_len"])
    return 100.0 * (need * tps / flops.peaks(obs["device"]["kind"])["bf16_flops"])
