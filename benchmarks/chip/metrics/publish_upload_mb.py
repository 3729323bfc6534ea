"""Host-to-device bytes per publish: the ``bytes`` of the window's
``upload`` spans (doc matrix, signatures and row patches sent by the
engine's refresh) over the ``publish`` spans of the window, in MB
(1e6 bytes)."""


def read(r):
    publishes = r.window_spans("publish")
    uploads = r.window_spans("upload")
    if not publishes or not uploads:
        return None
    return sum(int(s.args.get("bytes", 0)) for s in uploads) / len(
        publishes) / 1e6
