"""Host time to embed a query: the ``query_embed`` spans of the window
(tokenize, hash, scatter, signature, pack) over the queries they
embedded, in microseconds."""


def read(r):
    spans = r.window_spans("query_embed")
    queries = sum(int(s.args.get("queries", 0)) for s in spans)
    if not queries:
        return None
    return sum(s.dur_ns for s in spans) / queries / 1e3
