"""Operations and bytes of a kernel call, from the operands it was given.

A call's shapes come from its instruction text in the profiler trace
(``trace_reduce.operands``), so the count follows whatever dtype and
layout the program hands the kernel.
"""
from __future__ import annotations

from trace_reduce import nbytes

FLOATS = {"f64", "f32", "bf16", "f16", "f8e4m3fn", "f8e5m2"}


def call_bytes(outputs: list, operands: list) -> float:
    """Every operand read once and every output written once."""
    return sum(nbytes(t, d) for t, d in list(outputs) + list(operands))


def hsf_topk_flops(operands: list) -> float:
    """2 * B * N * D: the query batch [B, D] against the doc rows [N, D],
    the fewest and the most rows among the 2-D floating operands that
    share D.  The containment test and the top-k merge are not counted."""
    mats = [d for t, d in operands if t in FLOATS and len(d) == 2]
    if len(mats) < 2:
        raise ValueError(f"no query and doc operands in {operands}")
    docs = max(mats, key=lambda d: d[0])
    queries = min((d for d in mats if d[1] == docs[1]), key=lambda d: d[0])
    return 2.0 * queries[0] * docs[0] * docs[1]


def roofline_seconds(flops: float, nbytes_: float, peaks: dict) -> tuple:
    """(least time on the chip, which bound sets it)."""
    t_flops = flops / float(peaks["bf16_flops_per_s"])
    t_bytes = nbytes_ / float(peaks["hbm_bytes_per_s"])
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")
