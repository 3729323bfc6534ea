"""Share of the roofline reached by the fused top-k kernel
(``hsf_topk_batched``): over its calls in the traced window, the sum of
each call's least time on the chip, max(bytes / HBM bandwidth, 2*B*N*D /
peak), over the sum of their device times, in percent.  Bytes are the
operands and outputs each call was given, read from its instruction
text, so the count follows the dtype and layout of the call."""
import trace_reduce
import work

KERNEL = "hsf_topk_batched"


def read(r):
    if r.device is None:
        return None
    least = spent = 0.0
    for name, text, dur_ns in r.device["calls"]:
        if name != KERNEL:
            continue
        outs, args = trace_reduce.operands(text)
        t, _ = work.roofline_seconds(work.hsf_topk_flops(args),
                                     work.call_bytes(outs, args), r.peaks)
        least += t
        spent += dur_ns / 1e9
    if spent <= 0:
        return None
    return 100.0 * least / spent
