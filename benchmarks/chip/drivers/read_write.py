"""Reads and writes: an open loop of reads beside a stream of in-place
updates that one writer thread applies and publishes.

Parameters (traffic file): ``rate_of_knee`` (reads, as a share of the
configuration's knee), ``write_share`` (updates as a share of all
operations), ``write_zipf`` (popularity law of the updated passages).

The writer wakes at the first due update, applies every update that is
due with ``KnowledgeBase.add_text``, calls ``ServingRuntime.publish()``
and records, for each update, when the publish that made it visible
returned.  After each publish it looks up every code it just published,
so that visibility is checked on the served path.  Reads record how many
updates were visible when they were sent.
"""
from __future__ import annotations

import threading
import time

import numpy as np

from corpus import seed_rng
from load import Requests, open_loop, sleep_until
from traffic import arrival_times


def prepare(cell, seconds: float, stream: int) -> dict:
    rng = seed_rng(cell.seed, stream)
    rate = float(cell.traffic["rate_of_knee"]) * cell.knee
    reads = arrival_times(rate, seconds, rng)
    share = float(cell.traffic["write_share"])
    n_writes = int(round(len(reads) * share / (1.0 - share)))
    updates = cell.updates(n_writes)
    vocab = cell.corpus.vocab
    return {"seconds": seconds, "offsets": reads,
            "queries": cell.queries(stream).make(len(reads)),
            "write_offsets": np.sort(rng.uniform(0.0, seconds, n_writes)),
            "updates": updates,
            "texts": [" ".join([vocab[w] for w in u[1]]) for u in updates]}


def warm(cell, window: dict) -> None:
    """Publish the traffic file's ``warmup_publishes`` batches of updates,
    one publish each, so that a publish's own programs are compiled before
    the window.  Then compile the signature row patch for every
    power-of-two count of changed rows up to the window's updates: a
    publish patches the changed rows of the signature matrix, padded to
    the next power of two, and each new count compiles its own patch."""
    import jax.numpy as jnp

    vocab = cell.corpus.vocab
    for count in cell.traffic.get("warmup_publishes", []):
        updates = cell.updates(int(count))
        for row, words, _ in updates:
            cell.kb.add_text(cell.corpus.doc_id(row),
                             " ".join([vocab[w] for w in words]))
        gen = cell.runtime.publish()
        cell.applied.extend(updates)
        cell.published[gen] = len(cell.applied)
        cell.visible = len(cell.applied)
    sigs = jnp.zeros((cell.corpus.n, int(cell.config["engine"][
        "signature_words"])), jnp.int32)
    rows = 1
    while rows < 2 * max(1, len(window["updates"])):
        block = np.zeros((rows, sigs.shape[1]), np.int32)
        sigs.at[np.zeros(rows, np.int32)].set(jnp.asarray(block))
        rows *= 2


def _writer(cell, plan: dict, due: np.ndarray, published: np.ndarray,
            checks: Requests, failure: list) -> None:
    try:
        updates, n, j = plan["updates"], len(due), 0
        vocab = cell.corpus.vocab
        while j < n:
            sleep_until(due[j])
            jj = max(j + 1, int(np.searchsorted(due, time.perf_counter(),
                                                "right")))
            for w in range(j, jj):
                cell.kb.add_text(cell.corpus.doc_id(updates[w][0]),
                                 plan["texts"][w])
            gen = cell.runtime.publish()
            t = time.perf_counter()
            first = len(cell.applied)
            cell.applied.extend(updates[j:jj])
            cell.published[gen] = len(cell.applied)
            cell.visible = len(cell.applied)
            published[j:jj] = t
            for w in range(j, jj):
                checks.send(cell.runtime, cell.k,
                            (vocab[updates[w][2]], "update", first + w - j),
                            t, cell.visible)
            j = jj
    except BaseException as exc:  # noqa: BLE001 -- re-raised by drive()
        failure.append(exc)


def drive(cell, plan: dict, t0: float) -> dict:
    n = len(plan["queries"])
    reads = Requests(n, cell.keep(n, n))
    checks = Requests(len(plan["updates"]))
    due = t0 + plan["write_offsets"]
    published = np.full(len(due), np.nan)
    failure: list = []
    writer = threading.Thread(
        target=_writer, args=(cell, plan, due, published, checks, failure),
        name="bench-writer")
    writer.start()
    open_loop(cell.runtime, cell.k, reads, plan["offsets"], plan["queries"],
              t0, floor=lambda: cell.visible)
    t1 = t0 + plan["seconds"]
    sleep_until(t1)
    writer.join()
    if failure:
        raise failure[0]
    return {"reads": reads, "checks": checks, "t0": t0, "t1": t1,
            "writes": {"due": due, "published": published}}
