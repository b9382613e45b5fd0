"""The array form of a fields-free search reply.

A search with `fields == []` and no `sort` answers ids and scores only.
On both hops (partition server -> router -> client) such a reply rides
as four arrays over the binary tensor codec, never as a Python object
per hit:

    key_blob  uint8[sum(key_lens)]  the hits' keys, UTF-8, end to end
    key_lens  int32[hits]           bytes of each key
    counts    int32[queries]        hits of each query row (a row may
                                    hold fewer than k, or none)
    scores    float32[hits]         metric-oriented, query by query

beside `"columnar": true`. Hit `i` of the reply belongs to the query
whose running `counts` cover `i`; its key is the next `key_lens[i]`
bytes of `key_blob`. The serving processes (PS `_do_search`, router
`_search_scatter`) only pack, merge and forward these; rows of
`{"_id", "_score"}` are built by whoever asked for them: the SDK, or
the router for a caller that did not ask `columnar`.
"""

from __future__ import annotations

from typing import Any

import numpy as np

ARRAYS = ("key_blob", "key_lens", "counts", "scores")


def is_arrays(reply: dict) -> bool:
    return "key_blob" in reply


def form_of(reply: dict) -> str:
    """The reply's wire form, as `vearch_search_replies_total` labels it."""
    return "arrays" if is_arrays(reply) else "rows"


def reply_form_counter(registry):
    """A role's count of search replies by wire form, both series
    rendered from the first scrape."""
    counter = registry.counter(
        "vearch_search_replies_total",
        "search replies by wire form (arrays = ids and scores as four "
        "tensors, cluster/hitarrays.py; rows = a dict per hit)",
        ("form",))
    for form in ("arrays", "rows"):
        counter.inc(form, by=0.0)
    return counter


def pack(keys: list[str], counts: Any, scores: Any) -> dict:
    """Flat keys (query by query), hits per query and FLAT scores ->
    the array form. One join and one encode for all keys."""
    joined = "".join(keys)
    blob = joined.encode()
    if len(blob) == len(joined):  # ASCII: a key's bytes are its characters
        lens = np.fromiter(map(len, keys), np.int32, len(keys))
    else:
        lens = np.fromiter((len(k.encode()) for k in keys), np.int32,
                           len(keys))
    return {
        "columnar": True,
        "key_blob": np.frombuffer(blob, np.uint8),
        "key_lens": lens,
        # lint: allow[serving-blocking] the engine's host counts, or a list: no device involved
        "counts": np.asarray(counts, dtype=np.int32),
        # lint: allow[serving-blocking] the engine's result buffer, already host memory: terminal materialization for the wire codec
        "scores": np.asarray(scores, dtype=np.float32),
    }


def from_key_lists(keys: list[list[str]], scores: Any) -> dict:
    """A key-lists columnar partial (what a partition server answered
    before the array form) -> the array form."""
    return pack([k for ks in keys for k in ks], [len(ks) for ks in keys],
                # lint: allow[serving-blocking] wraps the wire-decoded score buffer (already host memory), no device involved
                np.asarray(scores).reshape(-1))


def from_rows(rows: list[list[dict]]) -> dict:
    return pack([r["_id"] for row in rows for r in row],
                [len(row) for row in rows],
                [r["_score"] for row in rows for r in row])


def keys_of(reply: dict) -> list[str]:
    """Every hit's key, in reply order."""
    raw = reply["key_blob"].tobytes()
    ends = np.cumsum(reply["key_lens"]).tolist()
    text = raw.decode()
    if len(text) == len(raw):  # ASCII: byte offsets are character offsets
        return [text[a:b] for a, b in zip([0] + ends, ends)]
    return [raw[a:b].decode() for a, b in zip([0] + ends, ends)]


def to_rows(reply: dict) -> list[list[dict]]:
    """The `documents` form of the same reply. A float32 score becomes
    the Python float that its JSON repr parses back to, so the rows are
    those a `documents` reply would have carried."""
    keys = keys_of(reply)
    scores = reply["scores"].tolist()
    rows, lo = [], 0
    for n in reply["counts"].tolist():
        rows.append([{"_id": k, "_score": s}
                     for k, s in zip(keys[lo:lo + n], scores[lo:lo + n])])
        lo += n
    return rows


def merge(partials: list[dict], k: int, start: int, size: int,
          reverse: bool) -> dict:
    """Top-k merge of array-form partials, then the page
    `[start, start + size)` of each query's top k. ONE sort for the
    whole reply, then a gather of the chosen hits and one of their key
    bytes. The arrays are small, so what a call into numpy costs is the
    call, and under load the hand-over of the interpreter lock that may
    come with it (PERF.md section 6, PR 26 and PR 28): their number
    does not grow with the rows, and both gathers are skipped where
    they would move nothing (every hit kept, in the order it came: a
    one-partition space whose rows hold at most k hits). Ties keep
    partition order, then the partition's own, as the row merge does."""
    nq = len(partials[0]["counts"])
    one = len(partials) == 1
    if one:
        cat = partials[0].__getitem__
        per_q = partials[0]["counts"]
    else:
        def cat(name):
            return np.concatenate([p[name] for p in partials])
        per_q = np.sum([p["counts"] for p in partials], axis=0)
    scores, lens = cat("scores"), cat("key_lens")
    rows = np.arange(nq)
    query = [np.repeat(rows, p["counts"]) for p in partials]
    # stable, on the NEGATED scores for descending order (reversing an
    # ascending sort would invert the ties)
    order = np.lexsort((-scores if reverse else scores,
                        query[0] if one else np.concatenate(query)))
    stop = min(k, start + size)
    if start == 0 and int(per_q.max(initial=0)) <= stop:
        keep, counts = order, per_q  # the window holds every hit
    else:
        counts = (np.minimum(per_q, stop) - start).clip(0)
        # sorted, a query's hits are one run of `order`: the window's
        # place in it, then each kept hit's place in the window
        lo = np.cumsum(per_q) - per_q + start - (np.cumsum(counts) - counts)
        keep = order[np.repeat(lo, counts) + np.arange(int(counts.sum()))]
    if one and keep is order and bool(
            (order == np.arange(order.size)).all()):
        return {"columnar": True, **{n: partials[0][n] for n in ARRAYS}}
    out_lens = lens[keep]
    ends = np.cumsum(out_lens)
    first = np.cumsum(lens) - lens  # where each key starts in the blob
    # byte j of the reply: its key's first byte + its place in that key
    take = (np.repeat(first[keep] - (ends - out_lens), out_lens)
            + np.arange(int(ends[-1]) if ends.size else 0))
    return {
        "columnar": True,
        "key_blob": cat("key_blob")[take],
        "key_lens": out_lens,
        "counts": counts.astype(np.int32),
        "scores": scores[keep],
    }
