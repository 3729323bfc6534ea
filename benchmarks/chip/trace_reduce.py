"""From a profiler trace to device busy and idle time, per-op and
per-kernel time by stable names, and idle gaps named by the host.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it.  On a TPU each chip is a plane
``/device:TPU:<n>`` whose line ``XLA Ops`` holds one event per HLO
instruction executed, named by the instruction's text
(``%hsf_topk_batched.1 = (f32[64,128]...) custom-call(f32[64,4096]... )``),
and whose line ``XLA Modules`` holds one event per program run.  Times
are nanoseconds on one base for host and device planes; the run marks
that base with a host annotation ``chipbench_mark`` taken at a known
``time.perf_counter_ns``, which puts the program's own spans on the same
clock.

- busy: the union of the ``XLA Ops`` intervals inside the window,
  averaged over the chips traced; idle share = 1 - busy / window;
- an op's name is its instruction name without the ``%`` and the
  numeric suffix, prefixed by the program that ran it
  (``jit__score_topk_pallas/hsf_topk_batched``);
- each piece of an idle gap is named by the innermost host span that
  covers it, or ``no host span`` where none does (the flusher is waiting
  for requests); the per-request stages are left out, since they tile a
  request's wait and do not say what the host was doing.
"""
from __future__ import annotations

import re

import numpy as np

MARK = "chipbench_mark"
_SUFFIX = re.compile(r"\.\d+$")
_SHAPE = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")
_ITEMSIZE = {"f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4, "u32": 4,
             "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
             "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1, "s4": 0.5, "u4": 0.5}


def op_name(event_name: str) -> str:
    """``%hsf_topk_batched.1 = (...) custom-call(...)`` ->
    ``hsf_topk_batched``."""
    head = event_name.split(" = ", 1)[0].strip().lstrip("%")
    return _SUFFIX.sub("", head)


def shapes(text: str) -> list:
    """[(dtype, dims)] of every array type written in ``text``."""
    return [(t, tuple(int(d) for d in dims.split(",") if d))
            for t, dims in _SHAPE.findall(text) if t in _ITEMSIZE]


_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
_ATTRS = re.compile(r"\),\s*[a-z_]+=")


def operands(event_name: str) -> tuple:
    """(outputs, operands) as [(dtype, dims)] from an instruction's text:
    the result type runs up to the opcode, the operands up to the first
    attribute (``), name=``) or the end."""
    rhs = event_name.partition(" = ")[2]
    m = _OPCODE.search(" " + rhs)
    if m is None:
        return [], []
    out_text, args = rhs[: m.start()], rhs[m.end() - 1:]
    a = _ATTRS.search(args)
    return shapes(out_text), shapes(args[: a.start()] if a else args)


def nbytes(dtype: str, dims: tuple) -> float:
    return float(np.prod(dims, dtype=np.float64)) * _ITEMSIZE[dtype]


def union_ns(intervals: np.ndarray, lo: float, hi: float) -> float:
    """Length of the union of [start, end) rows, clipped to [lo, hi)."""
    return float(sum(e - s for s, e in merged(intervals, lo, hi)))


def merged(intervals: np.ndarray, lo: float, hi: float) -> list:
    iv = np.clip(np.asarray(intervals, np.float64).reshape(-1, 2), lo, hi)
    iv = iv[iv[:, 1] > iv[:, 0]]
    out: list = []
    for s, e in iv[np.argsort(iv[:, 0], kind="stable")]:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def gaps(intervals: np.ndarray, lo: float, hi: float) -> list:
    """Idle [start, end) pieces of [lo, hi) outside the union."""
    out, at = [], lo
    for s, e in merged(intervals, lo, hi):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def load(path) -> dict:
    """{"mark_ns": host mark start or None, "chips": {plane: {"ops":
    [(name, start, dur)], "modules": [(name, start, dur)]}}}."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    out: dict = {"mark_ns": None, "chips": {}}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            chip = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key:
                    chip[key] = [(e.name, e.start_ns, e.duration_ns)
                                 for e in line.events]
            out["chips"][plane.name] = chip
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == MARK:
                        out["mark_ns"] = e.start_ns
    return out


def module_of(ops: list, modules: list) -> list:
    """Name of the program run that encloses each op ('' if none)."""
    if not modules:
        return [""] * len(ops)
    mods = sorted(modules, key=lambda m: m[1])
    starts = np.array([m[1] for m in mods])
    out = []
    for _, s, _ in ops:
        i = int(np.searchsorted(starts, s, "right")) - 1
        ok = i >= 0 and s < mods[i][1] + mods[i][2] + 1
        out.append(mods[i][0].split("(")[0] if ok else "")
    return out


# per-request stages tile each request's latency; they say what a request
# waited for, not what the host was doing, so gaps are not named by them
REQUEST_SPANS = {"request", "queue_wait", "flush_wait", "score", "merge"}


class HostSpans:
    """Host spans as sorted arrays, for naming idle gaps."""

    def __init__(self, spans: list):
        spans = sorted(spans, key=lambda x: x[1])
        self.names = [x[0] for x in spans]
        self.start = np.array([x[1] for x in spans], np.float64)
        self.end = np.array([x[2] for x in spans], np.float64)
        self.longest = float((self.end - self.start).max()) if spans else 0.0

    def split(self, lo: float, hi: float) -> dict:
        """{name: ns} of [lo, hi): each piece goes to the innermost
        (shortest) span covering it, or to ``no host span``."""
        a = int(np.searchsorted(self.start, lo - self.longest))
        b = int(np.searchsorted(self.start, hi))
        sel = (np.arange(a, b)[self.end[a:b] > lo]).tolist()
        cuts = sorted({lo, hi} | {float(x) for i in sel
                                  for x in (self.start[i], self.end[i])
                                  if lo < x < hi})
        out: dict = {}
        for p0, p1 in zip(cuts, cuts[1:]):
            cover = [i for i in sel
                     if self.start[i] <= p0 and self.end[i] >= p1]
            name = (self.names[min(cover, key=lambda i: self.end[i]
                                   - self.start[i])]
                    if cover else "no host span")
            out[name] = out.get(name, 0.0) + (p1 - p0)
        return out


def reduce(path, mark_pc_ns: int, t0: float, t1: float, spans=None,
           top: int = 10) -> dict:
    """Window [t0, t1) on ``time.perf_counter`` seconds -> busy_s,
    window_s, the ops' calls (name, instruction text, ns) and the
    breakdown."""
    tr = load(path)
    if tr["mark_ns"] is None or not tr["chips"]:
        return None          # no chip traced (a CPU rehearsal)
    shift = mark_pc_ns - tr["mark_ns"]   # trace ns -> perf_counter ns
    lo, hi = t0 * 1e9 - shift, t1 * 1e9 - shift
    busy, op_time, calls, idle = [], {}, [], {}
    host = HostSpans([(s.name, s.t0_ns - shift, s.t0_ns - shift + s.dur_ns)
                      for s in spans or []
                      if s.dur_ns > 0 and s.name not in REQUEST_SPANS])
    for chip in tr["chips"].values():
        ops = [o for o in chip["ops"] if lo <= o[1] < hi]
        iv = np.array([(s, s + d) for _, s, d in ops]).reshape(-1, 2)
        busy.append(union_ns(iv, lo, hi))
        for (name, s, d), mod in zip(ops, module_of(ops, chip["modules"])):
            key = f"{mod}/{op_name(name)}" if mod else op_name(name)
            op_time[key] = op_time.get(key, 0.0) + d
            calls.append((op_name(name), name, d))
        for g0, g1 in gaps(iv, lo, hi):
            for key, ns in host.split(g0, g1).items():
                idle[key] = idle.get(key, 0.0) + ns
    n = len(tr["chips"])
    def by_time(d):
        return sorted(d.items(), key=lambda x: -x[1])[:top]
    return {
        "busy_s": sum(busy) / n / 1e9,
        "window_s": (hi - lo) / 1e9,
        "calls": calls,
        "breakdown": {
            "device_ops": [[k, v / n / 1e9] for k, v in by_time(op_time)],
            "idle_gaps": [[k, v / n / 1e9] for k, v in by_time(idle)]},
    }
