"""Mean time a request of the window waited in the scheduler: its
``queue_wait`` (submit to dequeue) plus ``flush_wait`` (dequeue to the
start of scoring) spans, in ms."""


def read(r):
    queue = r.window_spans("queue_wait")
    flush = r.window_spans("flush_wait")
    if not queue:
        return None
    total = sum(s.dur_ns for s in queue) + sum(s.dur_ns for s in flush)
    return total / len(queue) / 1e6
