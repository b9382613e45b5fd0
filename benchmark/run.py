"""One run of one cell of BENCHMARK.json, on the served path.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This process is the chip's only owner while it serves: it starts master,
partition server and router in-process, brings the corpus up by the
product's restore (benchmark/corpus.py), warms the cell's own shapes
through the served entry, then lets generator processes that never
import jax (benchmark/loadgen.py) offer the cell's traffic for
`--seconds`. It refuses any platform but `tpu`, and any other number of
chips than the cell asks for. The last line of its standard output is
the one JSON object the benchmark's contract fixes; everything else goes
to standard error.

`--rehearse-cpu` runs the same path on the CPU backend at the
configuration's `rehearsal` size to find wrong paths before chip time is
spent: it prints no metric and exits with REHEARSAL_RC, never 0.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import threading
import time

T_BEGIN = time.monotonic()

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import numpy as np  # noqa: E402

from benchmark import cells, check, corpus, data, loadgen, stats  # noqa: E402
from benchmark import trace as trace_mod  # noqa: E402

REHEARSAL_RC = 4
#: seconds between spawning the generators and their first request
#: (interpreter start + imports), and of trace inside the window
SPAWN_LEAD_S = 2.5
TRACE_START_S, TRACE_SECONDS = 2.0, 3.0
DRAIN_S = 60.0


def log(msg: str, **kv) -> None:
    print(json.dumps({"t": round(time.monotonic() - T_BEGIN, 2), "msg": msg,
                      **kv}), file=sys.stderr, flush=True)


class Cluster:
    """Master + PS + router in-process, one space restored from a tree."""

    def __init__(self, cfg: dict, rows: int, run_dir: str):
        from vearch_tpu.cluster.standalone import StandaloneCluster
        from vearch_tpu.sdk.client import VearchClient

        self.cfg, self.rows = cfg, rows
        self.space = cfg["space"]["name"]
        self.cluster = StandaloneCluster(
            data_dir=os.path.join(run_dir, "cluster"), n_ps=1).start()
        self.client = VearchClient(self.cluster.router_addr)
        self.ps_addr = self.cluster.ps_nodes[0].addr
        self.client.create_database(corpus.DB)
        self.client.create_space(corpus.DB, corpus.space_config(cfg, rows))
        self.space_meta = self.client.get_space(corpus.DB, self.space)
        self.pid = str(self.space_meta["partitions"][0]["id"])

    def check_schema(self) -> None:
        """The shard was built on the schema this partition really has."""
        path = os.path.join(self.cluster.data_dir, "ps0",
                            f"partition_{self.pid}", "schema.json")
        with open(path) as f:
            have = json.load(f)
        want = corpus.table_schema_dict(self.cfg, self.rows)
        if have != want:
            raise RuntimeError(f"partition schema {have} is not the schema "
                               f"the shard was built on {want}")

    def restore(self, entry: str) -> dict:
        from vearch_tpu.cluster import rpc

        store = os.path.join(entry, "store")
        with open(os.path.join(
                store, "backup", corpus.DB, self.space, "v1", "space.json"),
                "w") as f:
            json.dump(self.space_meta, f)
        return rpc.call(
            self.cluster.master_addr, "POST",
            f"/backup/dbs/{corpus.DB}/spaces/{self.space}",
            {"command": "restore", "version": 1, "store_root": store},
            timeout=900.0)

    def serve_from(self, entry: str) -> dict:
        """Bring the corpus up as a deployment does: restore the tree, then
        require INDEXED with the full row count and apply the
        configuration's server settings."""
        self.check_schema()
        restored = self.restore(entry)
        self.require_indexed()
        self.apply_ps_config()
        return restored

    def stats(self) -> dict:
        from vearch_tpu.cluster import rpc

        return rpc.call(self.ps_addr, "GET", "/ps/stats")

    def apply_ps_config(self) -> None:
        """The configuration's runtime settings of the partition server,
        through the product's own /ps/engine/config."""
        from vearch_tpu.cluster import rpc

        cfg = self.cfg.get("ps_config")
        if not cfg:
            return
        rpc.call(self.ps_addr, "POST", "/ps/engine/config",
                 {"partition_id": int(self.pid), "config": cfg})
        want = cfg.get("quality", {}).get("sample_rate")
        have = self.stats()["quality"]["sampling"]["rate"]
        if want is not None and have != want:
            raise RuntimeError(f"shadow sampling rate reads {have}, the "
                               f"configuration states {want}")

    def require_indexed(self) -> None:
        from vearch_tpu.engine.types import IndexStatus

        part = self.stats()["partitions"][self.pid]
        if (part["status"] != int(IndexStatus.INDEXED)
                or part["doc_count"] != self.rows):
            raise RuntimeError(
                f"restored partition reads status {part['status']} with "
                f"{part['doc_count']} docs, expected INDEXED with {self.rows}")

    def search(self, queries: np.ndarray, profile: bool = True,
               filters: dict | None = None):
        s = self.cfg["search"]
        return self.client.search(
            corpus.DB, self.space,
            vectors=[{"field": self.cfg["vector_field"], "feature": queries}],
            limit=s["k"], filters=filters, fields=[],
            index_params=s["index_params"], profile=profile, cache=False)

    def warm(self, pool: np.ndarray, warm_rows: list[int],
             filters=()) -> None:
        """Every row count the mix can put into one dispatch, through the
        served entry (under each of `filters`, one of every class, where
        the mix filters); then the whole pool once, so that whichever
        queries the shadow-recall sampler picks have run their exact scan
        too. Each must name the configuration's dispatch path: a failed
        build serves brute force and would pass on answers alone."""
        tag = self.cfg["serving"]["dispatch_tag"]
        for rows in warm_rows:
            for flt in filters or [None]:
                for _ in range(2):
                    out = self.search(np.resize(pool, (rows, pool.shape[1])),
                                      filters=flt)
                    tags = out["profile"]["partitions"][self.pid][
                        "dispatches"]["tags"]
                    if tag not in tags:
                        raise RuntimeError(
                            f"{rows}-row request under {flt} served by "
                            f"{tags}, not {tag}")
        if self.stats()["quality"]["sampling"]["rate"] > 0:
            for lo in range(0, pool.shape[0], 64):
                self.search(pool[lo:lo + 64], profile=False)
            self.drain_shadow_sampler()

    def drain_shadow_sampler(self, timeout_s: float = 300.0) -> dict:
        """Wait until the PS's shadow-recall worker has finished every
        sampled request: its exact scans run on the device in a
        background thread (chip_smoke.py drain_shadow_sampler)."""
        deadline = time.monotonic() + timeout_s
        while True:
            q = self.stats()["quality"]
            c = q["sampling"]["counters"]
            finished = sum(c.get(k, 0) for k in (
                "executed", "dropped", "stale", "shed", "error"))
            if q["sampling"]["queue"] == 0 and finished >= c.get("sampled", 0):
                return {"rate": q["sampling"]["rate"], **c}
            if time.monotonic() > deadline:
                raise RuntimeError(f"shadow sampler still busy: {c}")
            time.sleep(0.1)

    def filter_cache_events(self) -> dict:
        """The PS's `vearch_ps_filter_cache_events_total{event}`, from its
        /metrics: what the engine's filter-mask cache answered."""
        import urllib.request

        name = "vearch_ps_filter_cache_events_total"
        with urllib.request.urlopen(f"http://{self.ps_addr}/metrics",
                                    timeout=30.0) as r:
            text = r.read().decode()
        return {line.split('event="')[1].split('"')[0]:
                float(line.rsplit(" ", 1)[1])
                for line in text.splitlines() if line.startswith(name + "{")}

    def write_read_delete(self, near: np.ndarray) -> int:
        """chip_smoke.py's guarantee check: an acknowledged write is read
        back by id and found by search; after an acknowledged delete it is
        gone from both. Returns how many of the steps failed."""
        cfg, new_id = self.cfg, "bench_new"
        vec = (near + 40.0).astype(np.float32)
        doc = {"_id": new_id, cfg["vector_field"]: vec,
               **{c["name"]: 1.0 for c in cfg.get("scalar_columns", [])}}
        bad = 0
        out = self.client.upsert(corpus.DB, self.space, [doc])
        bad += out["total"] != 1
        docs = self.client.query(corpus.DB, self.space,
                                 document_ids=[new_id], vector_value=True)
        bad += not (len(docs) == 1 and docs[0]["_id"] == new_id
                    and np.allclose(docs[0][cfg["vector_field"]], vec))
        hits = self.search(vec)["documents"]
        bad += not (hits and hits[0] and hits[0][0]["_id"] == new_id)
        bad += self.client.delete(corpus.DB, self.space,
                                  document_ids=[new_id]) != 1
        bad += self.client.query(corpus.DB, self.space,
                                 document_ids=[new_id]) != []
        hits = self.search(vec)["documents"]
        bad += any(h["_id"] == new_id for h in hits[0])
        return int(bad)

    def stop(self) -> None:
        """Stop the servers and wait for their daemon loops: an interpreter
        that exits while another thread is inside JAX aborts (rc 134;
        chip_smoke.py stop())."""
        self.cluster.stop()
        deadline = time.monotonic() + 15.0
        for t in threading.enumerate():
            if (t is not threading.current_thread()
                    and "process_request_thread" not in t.name):
                t.join(max(0.0, deadline - time.monotonic()))


def mix_filter(cfg: dict, mix: dict) -> dict:
    """The mix's `filter` with the modulo of the column it names, which
    the configuration's `scalar_columns` states."""
    flt = mix["filter"]
    cols = {c["name"]: c for c in cfg.get("scalar_columns", [])}
    if flt["column"] not in cols:
        raise KeyError(f"mix {mix['name']!r} filters on {flt['column']!r}; "
                       f"configuration {cfg['name']!r} has {sorted(cols)}")
    return {**flt, "modulo": cols[flt["column"]]["modulo"]}


def warm_filters(cfg: dict, mix: dict, seed: int) -> list[dict]:
    """A filter of each class of the mix, for the warm-up; none where the
    mix does not filter."""
    if not mix.get("filter"):
        return []
    flt, rng = mix_filter(cfg, mix), np.random.default_rng(seed)
    return [loadgen.filter_body(flt["column"], *loadgen.draw_filter(
        rng, {**flt, "classes": [c]})) for c in flt["classes"]]


def generator_specs(mix: dict, cfg: dict, router: str, pool_path: str,
                    run_dir: str, seed: int, t_start: float, t_stop: float,
                    profile: bool) -> list[dict]:
    """What each generator process of the mix is told (loadgen.py's
    spec). A mix without `filter` has no such key in its specs."""
    specs = []
    for w in range(int(mix["processes"])):
        spec = {
            "router": router, "db": corpus.DB, "space": cfg["space"]["name"],
            "field": cfg["vector_field"], "k": cfg["search"]["k"],
            "index_params": cfg["search"]["index_params"],
            "cache": bool(mix["cache"]), "profile": profile,
            "loop": mix["loop"], "rows": int(mix["rows_per_request"]),
            "threads": int(mix["threads"]), "seed": seed, "worker": w,
            "pool_path": pool_path, "t_start": t_start, "t_stop": t_stop,
            "drain_s": DRAIN_S,
            "out": os.path.join(run_dir, f"gen{w}.npz"),
        }
        if mix["loop"] == "open":
            spec["due_path"] = os.path.join(run_dir, f"due{w}.npy")
        if mix.get("filter"):
            spec["filter"] = mix_filter(cfg, mix)
        specs.append(spec)
    return specs


def spawn_generators(cell, cfg, router: str, pool_path: str, run_dir: str,
                     seed: int, seconds: float, profile: bool):
    """Start the mix's generator processes. Returns (procs, out paths,
    t0 of the window)."""
    mix = cell.traffic
    t_start = time.monotonic() + SPAWN_LEAD_S
    t0 = t_start + float(mix["warmup_s"])
    t_stop = t0 + seconds + 0.25
    procs, outs = [], []
    if mix["loop"] == "open":
        due = t_start + stats.open_loop_schedule(
            float(mix["rate_per_s"]), t_stop - t_start,
            int(mix["gaps_seed"]), seed)
    for spec in generator_specs(mix, cfg, router, pool_path, run_dir, seed,
                                t_start, t_stop, profile):
        w = spec["worker"]
        if mix["loop"] == "open":
            np.save(spec["due_path"], due[w::int(mix["processes"])])
        spec_path = os.path.join(run_dir, f"gen{w}.json")
        with open(spec_path, "w") as f:
            json.dump(spec, f)
        procs.append(subprocess.Popen(
            [sys.executable, os.path.join(HERE, "loadgen.py"), spec_path],
            stdout=sys.stderr))
        outs.append(spec["out"])
    return procs, outs, t0


def gather(outs: list[str]) -> dict:
    parts = [np.load(p) for p in outs]
    rec = {k: np.concatenate([p[k] for p in parts])
           for k in ("t_due", "t_send", "t_done", "ok", "q_idx", "ids",
                     "scores", "prof", "tags", "f_lo", "f_width")}
    rec["errors"] = [str(e) for p in parts for e in p["errors"]]
    return rec


class Obs:
    """What a metric's reader may read of one run."""

    def __init__(self, **kv):
        self.__dict__.update(kv)

    def prof(self, field: str) -> np.ndarray:
        """One profile column over the window's requests (traced run)."""
        return self.win["prof"][:, loadgen.PROFILE_FIELDS.index(field)]


def window_view(mix: dict, rec: dict, t0: float, seconds: float) -> dict:
    """The window's requests, latencies and counts, by the mix's loop."""
    if mix["loop"] == "closed":
        mask, lat = stats.closed_loop_window(
            rec["t_send"], rec["t_done"], rec["ok"], t0, seconds)
        in_win = stats.in_window(rec["t_done"], t0, seconds)
        attempted = int(in_win.sum())
        late = np.zeros(0)
    else:
        due, mask, lat = stats.open_loop_window(
            rec["t_due"], rec["t_done"], rec["ok"], t0, seconds)
        attempted = int(due.sum())
        late = stats.lateness_ms(rec["t_due"][due], rec["t_send"][due])
    win = {k: rec[k][mask] for k in ("t_due", "t_send", "t_done", "q_idx",
                                     "ids", "scores", "prof", "tags",
                                     "f_lo", "f_width")}
    return {"win": win, "lat_ms": lat, "attempted": attempted,
            "failed": attempted - int(mask.sum()), "late_ms": late}


class FilteredTruth:
    """The reference's answers under the filters a window sent: the exact
    top-k of every pool query under every distinct passing set, made
    after the window and kept beside `truth.npy` in a file of its own
    (`truth-filter-<column>.npz`; `path` None keeps nothing)."""

    def __init__(self, ref: data.ExactReference, queries: np.ndarray,
                 k: int, col: np.ndarray, path: str | None = None):
        self.ref, self.queries, self.k, self.col = ref, queries, k, col
        self.values, counts = np.unique(col, return_counts=True)
        #: rows holding one of the first i distinct values
        self.rows_below = np.concatenate([[0], np.cumsum(counts)])
        self.path = path
        self.sets = np.zeros((0, 2), np.int64)
        self.truth = np.zeros((0, queries.shape[0], k), np.int64)
        if path and os.path.exists(path):
            with np.load(path) as f:
                self.sets, self.truth = f["sets"], f["truth"]

    def of(self, lo: np.ndarray, width: np.ndarray) -> tuple[np.ndarray, dict]:
        """(truth [S, pool, k], the `filt` of check.compare) for requests
        that asked for `lo <= column < lo + width`."""
        hi = lo + width
        mine = data.range_sets(self.values, lo, hi)
        known = {tuple(s) for s in self.sets.tolist()}
        new = np.array(sorted({tuple(s) for s in mine.tolist()} - known),
                       np.int64).reshape(-1, 2)
        if new.size:
            t_ref = time.monotonic()
            self.truth = np.concatenate([self.truth, data.range_truth(
                self.ref, self.queries, self.k, self.col, new)])
            self.sets = np.concatenate([self.sets, new])
            if self.path:
                np.savez(self.path, sets=self.sets, truth=self.truth)
            log("filtered reference", sets=int(new.shape[0]),
                seconds=time.monotonic() - t_ref)
        index = {tuple(s): i for i, s in enumerate(self.sets.tolist())}
        set_idx = np.array([index[tuple(s)] for s in mine.tolist()], np.int64)
        return self.truth, {"col": self.col, "lo": lo, "hi": hi,
                            "set_idx": set_idx}

    def pass_share(self, lo: np.ndarray, width: np.ndarray,
                   classes: list[dict]) -> dict:
        """Requests and mean share of rows that passed, by class (a class
        is known by the width it asks)."""
        sets = data.range_sets(self.values, lo, lo + width)
        share = (self.rows_below[sets[:, 1]]
                 - self.rows_below[sets[:, 0]]) / self.col.size
        out = {}
        for c in classes:
            mine = width == float(c["width"])
            out[c["name"]] = {
                "requests": int(mine.sum()),
                "pass_share": float(share[mine].mean()) if mine.any() else None}
        return out


def judge(cfg: dict, mix: dict, rec: dict, t0: float, seconds: float,
          ref: data.ExactReference, queries: np.ndarray, truth: np.ndarray,
          filtered: FilteredTruth | None = None):
    """The window's view of the generators' records and the checks on what
    its requests themselves returned; with `filtered`, each request held
    to the truth of its own passing set (`truth` is not read)."""
    view = window_view(mix, rec, t0, seconds)
    win = view["win"]
    filt = None
    if filtered is not None:
        truth, filt = filtered.of(win["f_lo"], win["f_width"])
    checks, _ = check.compare(cfg, ref, queries, truth, win["q_idx"],
                              win["ids"], win["scores"], filt)
    return view, checks


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--control", action="store_true",
                    help="also read the lower-precision control's numbers "
                         "(benchmark/control.py); not part of a run")
    args = ap.parse_args(argv)

    cell = cells.Cell(args.workload)
    cfg, mix = cell.config, cell.traffic
    seed = args.seed % (2 ** 32)
    rows = int(cfg["rows"])
    if args.rehearse_cpu:
        reh = cfg["rehearsal"]
        rows = int(reh["rows"])
        for f in cfg["space"]["fields"]:
            if f.get("index"):
                f["index"]["params"]["ncentroids"] = reh["ncentroids"]
    run_dir = os.path.join(corpus.CACHE, f"run-{cell.name}")
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)

    # -- set-up ---------------------------------------------------------------
    made: dict = {}
    maker = threading.Thread(
        target=lambda: made.update(zip(("base", "queries", "q_rows"),
                                       data.make_data(cfg, seed, rows))),
        name="make_data")
    maker.start()  # numpy only; the reference's rows, beside the build
    entry, built = corpus.ensure(cfg, seed, rows, args.rehearse_cpu)
    log("corpus", **built)

    import jax  # the build child, if any, has exited: the chip is free

    from vearch_tpu.ops import perf_model
    from vearch_tpu.utils import enable_compilation_cache

    cache_dir = enable_compilation_cache()
    compiles: list[float] = []  # when the backend compiled anything at all

    def on_duration(name: str, *_a, **_kw) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            compiles.append(time.monotonic())

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    rehearsal = args.rehearse_cpu and device["platform"] == "cpu"
    if device["platform"] != "tpu" and not rehearsal:
        print(f"benchmark: no TPU: jax found {device}", file=sys.stderr)
        return 1
    if device["count"] != cell.chips and not rehearsal:
        print(f"benchmark: cell asks for {cell.chips} chip(s), jax sees "
              f"{device['count']}", file=sys.stderr)
        return 1
    peak = None if rehearsal else cells.peaks(device["kind"])
    log("device", **device, compile_cache_dir=cache_dir)

    cl = Cluster(cfg, rows, run_dir)
    try:
        restored = cl.serve_from(entry)
        log("restored", **restored["partitions"][0])
        maker.join()
        base, queries = made["base"], made["queries"]
        flt = mix_filter(cfg, mix) if mix.get("filter") else None
        cl.warm(queries, [int(r) for r in mix["warm_rows"]],
                warm_filters(cfg, mix, seed))
        # read outside the window: a scrape is work on the servers' interpreter
        mask_cache = cl.filter_cache_events() if flt else None
        log("warmed", programs=perf_model.total_compiled_programs())
        pool_path = os.path.join(run_dir, "pool.npy")
        np.save(pool_path, queries)

        procs, outs, t0 = spawn_generators(
            cell, cfg, cl.cluster.router_addr, pool_path, run_dir, seed,
            args.seconds, profile=bool(args.trace))
        time.sleep(max(0.0, t0 - time.monotonic()))

        # -- the window -------------------------------------------------------
        setup_s = t0 - T_BEGIN
        programs_before = perf_model.compiled_program_counts()
        trace_dir = os.path.join(run_dir, "trace")
        mark_ns = None
        if args.trace:
            span = min(TRACE_SECONDS, max(0.5, args.seconds - TRACE_START_S - 0.5))
            time.sleep(min(TRACE_START_S, args.seconds / 4))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            mark_ns = time.monotonic_ns()
            with jax.profiler.TraceAnnotation(trace_mod.MARK):
                time.sleep(span)
            mark_end_ns = time.monotonic_ns()
            jax.profiler.stop_trace()
        time.sleep(max(0.0, t0 + args.seconds - time.monotonic()))
        programs_after = perf_model.compiled_program_counts()
        grew = {k: [programs_before.get(k, 0), v]
                for k, v in programs_after.items()
                if v != programs_before.get(k, 0)}
        n_compiled = sum(t0 <= t < t0 + args.seconds for t in compiles)
        if n_compiled:
            grew["backend_compiles"] = [0, n_compiled]

        for p in procs:
            rc = p.wait(timeout=DRAIN_S + 30.0)
            if rc != 0:
                raise RuntimeError(f"a load generator exited with {rc}")
        rec = gather(outs)
        if flt:
            after = cl.filter_cache_events()
            log("filter mask cache", after_warm_up=mask_cache,
                after_generators=after,
                hits_between=after.get("hit", 0.0) - mask_cache.get("hit", 0.0))
        mem = [d.memory_stats() or {} for d in devs]
        memory_peak = max((m.get("peak_bytes_in_use") or 0) for m in mem)
        wrd_failed = cl.write_read_delete(queries[1])
        sampler = cl.drain_shadow_sampler()
        log("window closed", requests=int(rec["ok"].size), sampler=sampler,
            window_compiles=grew, errors=rec["errors"][:3])
    finally:
        cl.stop()
        for p in locals().get("procs", []):
            if p.poll() is None:
                p.kill()
                p.wait()
        shutil.rmtree(os.path.join(run_dir, "cluster"), ignore_errors=True)

    # -- after the window: reference, comparison, metrics -----------------------
    ref = data.ExactReference(base, cfg["metric"])
    truth_path = os.path.join(entry, "truth.npy")
    filtered = None
    if flt:  # held to the truth of each request's passing set, not this one
        truth = None
        col = data.scalar_column(flt, rows)
        filtered = FilteredTruth(
            ref, queries, int(cfg["search"]["k"]), col,
            os.path.join(entry, f"truth-filter-{flt['column']}.npz"))
    elif os.path.exists(truth_path):
        truth = np.load(truth_path)
    else:
        t_ref = time.monotonic()
        truth = ref.topk(queries, int(cfg["search"]["k"]))
        np.save(truth_path, truth)
        log("reference", seconds=time.monotonic() - t_ref)
    view, checks = judge(cfg, mix, rec, t0, args.seconds, ref, queries, truth,
                         filtered)
    win = view["win"]
    if filtered is not None:
        log("filters in the window",
            **filtered.pass_share(win["f_lo"], win["f_width"],
                                  flt["classes"]))
    checks["write_read_delete_failed"] = {"value": wrd_failed, "limit": 0,
                                          "op": "<="}
    checks["window_compiles"] = {"value": len(grew), "limit": 0, "op": "<="}
    if args.control:
        from benchmark import control

        control.report(cfg, ref, queries, truth, win, filtered)

    obs = Obs(cell=cell, config=cfg, traffic=mix, seconds=args.seconds,
              setup_s=setup_s, recall=checks["recall_at_10"]["value"],
              memory_peak_bytes=memory_peak, peak=peak, rows=rows,
              rec=rec, t0=t0, trace=None, **view)
    result_device = {**device, "memory_peak_bytes": int(memory_peak)}
    breakdown = None
    if args.trace:
        tr = trace_mod.compact(trace_mod.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        start = trace_mod.mark_start_ns(tr)
        if start is None:
            raise RuntimeError("the trace does not hold the harness's mark")
        lo, hi = start, start + (mark_end_ns - mark_ns)
        obs.trace, obs.trace_lo_ns, obs.trace_hi_ns = tr, lo, hi
        obs.trace_offset_ns = mark_ns - start
        busy = trace_mod.busy_seconds(tr, lo, hi)
        if not busy and not rehearsal:  # the CPU backend has no device plane
            raise RuntimeError("no operation ran on the device in the trace")
        result_device.update(busy_s=busy, window_s=(hi - lo) / 1e9)
        gaps = trace_mod.idle_gaps(tr, lo, hi, n=1_000_000)
        breakdown = {
            "device_ops": [[name[:160], sec] for name, sec in
                           trace_mod.top_ops(tr, lo, hi, 10)],
            "idle_gaps": trace_mod.name_gaps(
                gaps, obs.trace_offset_ns, rec["t_send"], rec["t_done"])[:10],
        }
        if os.environ.get("BENCH_KEEP_TRACE"):
            with open(os.environ["BENCH_KEEP_TRACE"], "w") as f:
                json.dump(tr, f)

    metrics = {}
    for m in (cell.per_layer if args.trace else cell.end_to_end):
        value = cells.metric_reader(m["name"])(obs)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    worst = int(np.argmax(view["lat_ms"])) if view["lat_ms"].size else 0
    log("generator", late_ms_p99=(stats.percentile(view["late_ms"], 99)
                                  if view["late_ms"].size else 0.0),
        worst_ms=float(view["lat_ms"][worst]) if view["lat_ms"].size else None,
        worst_at_s=float(win["t_send"][worst] - t0) if view["lat_ms"].size
        else None,
        lat_ms_percentiles={str(q): stats.percentile(view["lat_ms"], q)
                            for q in (50, 90, 95, 97, 98, 99, 99.5, 99.9)}
        if view["lat_ms"].size else None,
        over_100ms=int((view["lat_ms"] > 100).sum()),
        requests_in_window=int(view["lat_ms"].size),
        rows_done=int(win["q_idx"].size))
    correct = check.report(checks)
    if rehearsal:
        print(json.dumps({"rehearsal": True, "correct": correct,
                          "attempted": view["attempted"],
                          "failed": view["failed"],
                          "metrics_reported": sorted(metrics),
                          "checks": checks}), file=sys.stderr, flush=True)
        return REHEARSAL_RC
    result = {"correct": correct, "attempted": view["attempted"],
              "failed": view["failed"], "metrics": metrics,
              "device": result_device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
