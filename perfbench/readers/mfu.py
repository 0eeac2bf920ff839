"""Model FLOP/s utilization: the model's operations per token
(`perfbench/ops/transformer_train.py`) times tokens per second per chip,
over the chip's bf16 peak (`perfbench/peaks.json`). Not a kernel's
roofline share, and blind to idle time."""


def read(spec, evidence):
    c = evidence.counters
    peak = evidence.peaks.get("bf16_flops_per_s")
    if not peak or "train.tokens_per_s_per_chip" not in c:
        return None
    return 100.0 * c["train.flops_per_token"] \
        * c["train.tokens_per_s_per_chip"] / peak
