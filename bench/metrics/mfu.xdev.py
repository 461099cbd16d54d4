"""Model FLOP utilisation of the cohort's local training: the CNN's
forward and backward FLOPs per sample from its shapes times the samples
trained in the window (plus the forward passes of the loss votes), over
peak bf16 FLOP/s x the window's host-clock length."""

from bench import metric_math


def read(record):
    per_round = record.facts.get("model_flops_per_round")
    if not per_round:
        return None
    return metric_math.mfu_pct(record, per_round * len(record.window.work))
