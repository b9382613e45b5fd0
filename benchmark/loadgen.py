"""Load generator: one process, a few threads, the SDK client over HTTP.

Runs as a process of its own that never imports jax (the SDK client
needs only numpy and cluster/rpc), so client-side encoding does not share
the servers' interpreter lock. One general generator reads a traffic
mix's parameters: a closed loop (each thread is a caller that waits for
its reply) or an open loop (requests are due at times fixed beforehand
and are timed from then). Every answer is kept, so the harness can
compare what the timed requests themselves returned. A mix may state a
`filter`: each request then carries one range filter over a scalar
column, drawn from the caller's own stream (`draw_filter`).

    python benchmark/loadgen.py <spec.json>   ->  writes spec["out"] (.npz)

All times are `time.monotonic()`, which is one clock for every process
of a machine.
"""

from __future__ import annotations

import json
import os
import queue
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: profile numbers kept per request in a traced run (see PROFILE_KEYS in
#: metrics readers): the slowest partition's, since it blocks the reply
PROFILE_FIELDS = ("rpc_ms", "merge_ms", "ps_total_ms", "ps_queue_ms",
                  "ps_gate_wait_ms", "dispatch_sum_ms", "dispatches")


def parse_profile(prof: dict) -> tuple[list[float], str]:
    """Flatten one router profile to PROFILE_FIELDS + its dispatch tags."""
    part = max(prof["partitions"].values(), key=lambda p: p["rpc_ms"])
    ph = part.get("phases") or {}
    disp = part.get("dispatches") or {}
    per = disp.get("per_dispatch_ms") or {}  # tag -> ms, summed per tag
    return ([float(part["rpc_ms"]), float(prof.get("merge_ms") or 0.0),
             float(ph.get("total", np.nan)), float(ph.get("queue", 0.0)),
             float(ph.get("gate_wait", 0.0)), float(sum(per.values())),
             float(disp.get("count") or 0)],
            ",".join(disp.get("tags") or []))


#: a drawn bound keeps this far from a whole number: the column holds
#: whole numbers (row i: i % modulo), so a float32 and a float64
#: evaluation of `lo <= column < hi` then pass the same rows
BOUND_MARGIN = 2.0 ** -10


def draw_filter(rng: np.random.Generator, flt: dict) -> tuple[float, float]:
    """(lo, width) of one request's filter: a class by weight, then `lo`
    uniformly from the continuous [0, modulo - width). Because `lo` is
    continuous no two requests carry the same filter, so a cache keyed
    on the filter cannot answer for its evaluation; because row i holds
    i % modulo, a whole `width` passes width / modulo of the rows
    whatever `lo`."""
    classes = flt["classes"]
    weights = np.asarray([c["weight"] for c in classes], np.float64)
    width = float(classes[rng.choice(len(classes),
                                     p=weights / weights.sum())]["width"])
    room = float(flt["modulo"]) - width
    if not (width > 0 and room > 4 * BOUND_MARGIN):
        raise ValueError(f"filter width {width} leaves no room for a lower "
                         f"bound under modulo {flt['modulo']}")
    while True:
        lo = float(rng.uniform(0.0, room))
        if all(abs(b - round(b)) >= BOUND_MARGIN for b in (lo, lo + width)):
            return lo, width


def filter_body(column: str, lo: float, width: float) -> dict:
    """`lo <= column < lo + width` in the product's own filter form."""
    return {"operator": "AND", "conditions": [
        {"field": column, "operator": ">=", "value": lo},
        {"field": column, "operator": "<", "value": lo + width}]}


class Recorder:
    """Per-request records of one generator process."""

    def __init__(self, rows: int, k: int):
        self.rows, self.k = rows, k
        self.lock = threading.Lock()
        self.t_due, self.t_send, self.t_done, self.ok = [], [], [], []
        self.q_idx, self.ids, self.scores, self.prof, self.tags = \
            [], [], [], [], []
        self.f_lo, self.f_width = [], []
        self.errors: list[str] = []

    def add(self, t_due, t_send, t_done, q_idx, docs, prof, err,
            f_lo=np.nan, f_width=np.nan):
        ids = np.full((self.rows, self.k), -1, np.int64)
        scores = np.full((self.rows, self.k), np.nan, np.float64)
        # a reply that came is judged by what it says: rows or hits it
        # lacks stay -1 and are for `correct`, not for `failed`
        ok = err is None and docs is not None
        if ok:
            for i, row in enumerate(docs[:self.rows]):
                for j, h in enumerate(row[:self.k]):
                    key = h["_id"]
                    ids[i, j] = int(key[3:]) if key.startswith("doc") else -2
                    scores[i, j] = h["_score"]
        vals, tags = (parse_profile(prof) if ok and prof
                      else ([np.nan] * len(PROFILE_FIELDS), ""))
        with self.lock:
            self.t_due.append(t_due)
            self.t_send.append(t_send)
            self.t_done.append(t_done)
            self.ok.append(ok)
            self.q_idx.append(q_idx)
            self.ids.append(ids)
            self.scores.append(scores)
            self.prof.append(vals)
            self.tags.append(tags)
            self.f_lo.append(f_lo)
            self.f_width.append(f_width)
            if err is not None and len(self.errors) < 5:
                self.errors.append(err)

    def arrays(self) -> dict:
        n = len(self.ok)
        return {
            "t_due": np.asarray(self.t_due, np.float64),
            "t_send": np.asarray(self.t_send, np.float64),
            "t_done": np.asarray(self.t_done, np.float64),
            "ok": np.asarray(self.ok, bool),
            "q_idx": np.asarray(self.q_idx, np.int64).reshape(n, self.rows),
            "ids": np.asarray(self.ids, np.int64).reshape(n, self.rows, self.k),
            "scores": np.asarray(self.scores, np.float64).reshape(
                n, self.rows, self.k),
            "prof": np.asarray(self.prof, np.float64).reshape(
                n, len(PROFILE_FIELDS)),
            "tags": np.asarray(self.tags, dtype=str),
            "f_lo": np.asarray(self.f_lo, np.float64),
            "f_width": np.asarray(self.f_width, np.float64),
            "errors": np.asarray(self.errors, dtype=str),
        }

    def save(self, path: str) -> None:
        np.savez(path, **self.arrays())


def make_sender(spec: dict, pool: np.ndarray, rec: Recorder):
    from vearch_tpu.cluster import rpc
    from vearch_tpu.sdk.client import VearchClient

    client = VearchClient(spec["router"])
    # a refused request is a failed request: no client-side retry sleeps
    # inside a timed window
    client.max_retries_429 = 0

    column = (spec.get("filter") or {}).get("column")

    def send(q_idx: np.ndarray, t_due: float,
             flt: tuple[float, float] | None = None) -> None:
        """One request; `flt` is its (lo, width) where the mix filters."""
        filters = filter_body(column, *flt) if flt else None
        t_send = time.monotonic()
        docs = prof = err = None
        try:
            out = client.search(
                spec["db"], spec["space"],
                vectors=[{"field": spec["field"], "feature": pool[q_idx]}],
                limit=spec["k"], filters=filters, fields=[],
                index_params=spec["index_params"],
                profile=spec["profile"], cache=spec["cache"])
            if spec["profile"]:
                docs, prof = out["documents"], out["profile"]
            else:
                docs = out
        except (rpc.RpcError, OSError, KeyError, ValueError) as e:
            err = f"{type(e).__name__}: {e}"
        t_done = time.monotonic()
        rec.add(t_due, t_send, t_done, q_idx, docs, prof, err,
                *(flt or ()))

    return send


def run_closed(spec: dict, pool: np.ndarray, rec: Recorder) -> None:
    """Each thread is one caller: next request when the reply is in."""
    def caller(tid: int) -> None:
        send = make_sender(spec, pool, rec)
        rng = np.random.default_rng([spec["seed"], spec["worker"], tid])
        while time.monotonic() < spec["t_start"]:
            time.sleep(0.0005)
        flt = spec.get("filter")
        while (now := time.monotonic()) < spec["t_stop"]:
            q_idx = rng.integers(0, pool.shape[0], spec["rows"])
            send(q_idx, now, draw_filter(rng, flt) if flt else None)

    threads = [threading.Thread(target=caller, args=(i,), name=f"caller{i}")
               for i in range(spec["threads"])]
    for t in threads:
        t.start()
    for t in threads:
        t.join()


def run_open(spec: dict, pool: np.ndarray, rec: Recorder) -> None:
    """Requests are due at fixed times; a dispatcher hands each to the
    next free sender thread the moment it is due."""
    due = np.load(spec["due_path"])  # absolute monotonic seconds
    rng = np.random.default_rng([spec["seed"], spec["worker"]])
    picks = rng.integers(0, pool.shape[0], (due.size, spec["rows"]))
    flt = spec.get("filter")
    filters = [draw_filter(rng, flt) for _ in due] if flt else None
    work: queue.Queue = queue.Queue()

    def sender() -> None:
        send = make_sender(spec, pool, rec)
        while (item := work.get()) is not None:
            send(picks[item], float(due[item]),
                 filters[item] if filters else None)

    threads = [threading.Thread(target=sender, name=f"sender{i}")
               for i in range(spec["threads"])]
    for t in threads:
        t.start()
    for i, t_due in enumerate(due):
        while (wait := t_due - time.monotonic()) > 0:
            time.sleep(min(wait, 0.002) if wait > 0.0005 else 0)
        work.put(i)
    for _ in threads:
        work.put(None)
    deadline = time.monotonic() + spec["drain_s"]
    for t in threads:
        t.join(max(0.0, deadline - time.monotonic()))
    if any(t.is_alive() for t in threads):
        raise SystemExit("loadgen: requests still unanswered after drain")


def main(argv: list[str]) -> int:
    with open(argv[1]) as f:
        spec = json.load(f)
    pool = np.load(spec["pool_path"])
    rec = Recorder(spec["rows"], spec["k"])
    (run_closed if spec["loop"] == "closed" else run_open)(spec, pool, rec)
    rec.save(spec["out"])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
