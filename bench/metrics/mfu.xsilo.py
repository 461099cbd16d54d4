"""Model FLOP utilisation of local training: training FLOPs per token
from the decoder's shapes (6 per matmul parameter plus causal attention)
times the window's tokens, over chips x peak bf16 FLOP/s x the window's
host-clock length."""

from bench import metric_math


def read(record):
    per_token = record.facts.get("train_flops_per_token")
    if not per_token:
        return None
    return metric_math.mfu_pct(record, per_token * record.window.total("tokens"))
