"""Shared arithmetic of the metric readers: each reader in metrics/
reads one metric from a run's record (``bench.harness.RunRecord``) and
returns None where the run holds nothing to read."""

from __future__ import annotations


def peak(record, key: str) -> float:
    kind = record.peaks.get(record.device_kind)
    if kind is None:
        raise KeyError(f"no peaks for device kind {record.device_kind!r} in peaks.json")
    return float(kind[key])


def complete_trace(record):
    """The run's trace reduction where it holds the whole window, else
    None: a trace the profiler cut short has nothing to read."""
    t = record.trace
    return t if t is not None and t.complete else None


def idle_pct(record):
    t = complete_trace(record)
    return None if t is None else 100.0 * t.idle_share


def kernel_roofline_pct(record, kernel: str):
    """Share of the kernel's HBM roofline: the bytes its calls must move
    at the chip's peak bandwidth over the device time of its operations.
    The kernels move a few bytes per operation, so bandwidth bounds them.
    The trace holds every round of the window."""
    trace = complete_trace(record)
    if trace is None:
        return None
    t = trace.kernel_s.get(kernel)
    if not t:
        return None
    per_round = record.facts.get("kernel_bytes_per_round", {}).get(kernel)
    if per_round is None:
        return None
    need_s = per_round * len(record.window.work) / peak(record, "hbm_bytes_per_s")
    return 100.0 * need_s / t


def mfu_pct(record, flops: float):
    """The window's FLOPs over chips x peak x the window's host-clock
    length."""
    return 100.0 * flops / (
        record.chips * peak(record, "flops_bf16") * record.window.window_s
    )
