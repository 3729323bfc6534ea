"""Rows scored per IVF dispatch as a share of the corpus: the window's
``ivf_widen_round`` spans, each holding the rows one round scored (the
candidate rows it gathered, or all N where the probe set collapsed to
the flat scan), summed over the dispatch's rounds and averaged over the
dispatches (those whose first round is in the window), over N, in
percent.  Near 100% the cell is on the flat-collapse side of the exact
search; well under it, on the gather side."""


def read(r):
    rounds = r.window_spans("ivf_widen_round")
    calls = sum(1 for s in rounds if int(s.args.get("round", 0)) == 1)
    n = r.cell.corpus.n if r.cell is not None else 0
    if not calls or not n:
        return None
    rows = sum(int(s.args.get("rows", 0)) for s in rounds)
    return 100.0 * rows / calls / n
