"""Share of the HBM roofline reached by the IVF candidate gather
(``index/ivf.py`` ``_gather_rows``: the probed clusters' rows and
signatures taken from the resident doc planes): over its calls in the
traced window, the sum of each call's least time, bytes / HBM
bandwidth, over the sum of their device times, in percent.

The compiler fuses each of the two gathers (rows, signatures) into an
op named ``fusion``, so a call is found by its shapes: each output
[R, C] has a source operand [M, C] of its dtype with M >= R, and an
integer operand of R entries holds the row indices.  R is at least
MIN_ROWS, the program's smallest candidate block; the kernel's own
gather of a batch's k selected rows (at most 64 x 10 = 640 rows in the
cells that read this) is left out.

A gather reads only the rows it selects, so its bytes are its own:
every output written once and read once from the source (2 x the
output bytes) plus the index vector.  The source operands, whose whole
[N, D] matrix the instruction text names, are not counted (that would
read the 1.1 GB matrix as moved on every call)."""
import trace_reduce

INT_TYPES = {"s32", "u32", "s64"}
MIN_ROWS = 1024


def gather_bytes(outputs: list, operands: list):
    """Bytes a candidate-row gather moves, or None where the call is none
    (see the module's docstring for how one is recognised)."""
    if not outputs or any(len(d) != 2 for _, d in outputs):
        return None
    rows = outputs[0][1][0]
    if rows < MIN_ROWS or any(d[0] != rows for _, d in outputs):
        return None
    for t, d in outputs:
        if not any(t2 == t and len(d2) == 2 and d2[1] == d[1]
                   and d2[0] >= rows for t2, d2 in operands):
            return None
    index = [(t, d) for t, d in operands if t in INT_TYPES
             and trace_reduce.nbytes(t, d) == rows * (8 if t == "s64" else 4)
             and (len(d) == 1 or d[-1] == 1)]
    if not index:
        return None
    out = sum(trace_reduce.nbytes(t, d) for t, d in outputs)
    return 2.0 * out + trace_reduce.nbytes(*index[0])


def read(r):
    if r.device is None:
        return None
    least = spent = 0.0
    for _, text, dur_ns in r.device["calls"]:
        outs, args = trace_reduce.operands(text)
        moved = gather_bytes(outs, args)
        if moved is None:
            continue
        least += moved / float(r.peaks["hbm_bytes_per_s"])
        spent += dur_ns / 1e9
    if spent <= 0:
        return None
    return 100.0 * least / spent
