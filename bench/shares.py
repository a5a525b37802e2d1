"""Metric arithmetic shared by the readers: percentiles, idle share,
roofline and mfu shares.  A share that has nothing to read is None."""

from __future__ import annotations

import numpy as np

from bench import counts


def percentile(values, q: float):
    """The q-th percentile (linear interpolation), None for no values."""
    values = np.asarray(values, float)
    return float(np.percentile(values, q)) if values.size else None


def idle_pct(ctx):
    ts = ctx.trace_summary
    if not ts or ts["busy_s"] <= 0 or ts["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - ts["busy_s"] / ts["window_s"])


def kernel_roofline_pct(ctx, kernel: str, per_call: dict):
    """Least time of the traced calls over their measured time, in %."""
    ts = ctx.trace_summary
    if not ts or ctx.peak is None:
        return None
    k = ts["kernels"].get(kernel)
    if not k or k["events"] == 0 or k["seconds"] <= 0:
        return None
    least, _ = counts.roofline_seconds(per_call["ops"], per_call["bytes"], ctx.peak)
    return 100.0 * k["events"] * least / k["seconds"]


def mfu_pct(ctx, required_ops: float):
    """Required operations over the window's time at the bf16 peak, in %."""
    window = ctx.record.get("window_s", 0.0)
    if ctx.peak is None or window <= 0 or required_ops <= 0:
        return None
    return 100.0 * required_ops / window / ctx.peak["bf16_flops_per_s"]
