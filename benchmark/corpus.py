"""Corpus set-up: bulk build off the served path, then the product's own
restore.

A run is a new process and pays the whole set-up, so the corpus does not
arrive over REST (2,400 docs/s, PERF.md): the first run of a
(configuration, seed) in a checkout builds the shard in a child process
(an Engine on the partition's schema, bulk `upsert`, the index built by
`training_threshold`, `dump()`), writes it as a backup tree in the
default deduplicating layout and keeps it under
`benchmark/.cache/<config>-<seed>-<rows>/`. Every run, first or later,
brings the corpus up with `POST /backup/dbs/<db>/spaces/<space>
{"command": "restore"}`, as a deployment does.

The child owns the chip while it builds and exits before the parent
first touches jax, so the build's device memory is gone before serving
starts.

    python benchmark/corpus.py <spec.json>      # the child's entry
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
if REPO not in sys.path:
    sys.path.insert(0, REPO)

CACHE = os.path.join(HERE, ".cache")
DB = "bench"
INGEST_BATCH = 50_000


def space_config(cfg: dict, rows: int) -> dict:
    """The configuration's space, as `create_space` takes it. The index
    trains itself when the row count reaches `training_threshold`: on the
    last bulk batch."""
    space = json.loads(json.dumps(cfg["space"]))
    for f in space["fields"]:
        if f.get("index"):
            f["index"]["params"]["training_threshold"] = rows
    return space


def table_schema_dict(cfg: dict, rows: int) -> dict:
    """The partition's schema.json for this space, worked out without a
    cluster (the build runs before one exists); `Cluster.check_schema`
    holds it to the partition's own file afterwards."""
    from vearch_tpu.engine.types import TableSchema

    space = space_config(cfg, rows)
    return TableSchema.from_dict(
        {"name": space["name"], "fields": space["fields"]}).to_dict()


def entry_dir(cfg: dict, seed: int, rows: int) -> str:
    return os.path.join(CACHE, f"{cfg['name']}-{seed}-{rows}")


def ensure(cfg: dict, seed: int, rows: int, rehearsal: bool) -> tuple[str, dict]:
    """Cached backup tree of (config, seed, rows); built by a child if
    missing. Keeps one tree per configuration and evicts the others."""
    entry = entry_dir(cfg, seed, rows)
    meta_path = os.path.join(entry, "meta.json")
    if os.path.exists(meta_path):
        with open(meta_path) as f:
            return entry, {**json.load(f), "cache": "hit"}
    os.makedirs(CACHE, exist_ok=True)
    for name in os.listdir(CACHE):
        if name.startswith(cfg["name"] + "-"):
            shutil.rmtree(os.path.join(CACHE, name), ignore_errors=True)
    os.makedirs(entry)
    spec = {"config": cfg, "seed": seed, "rows": rows, "entry": entry,
            "rehearsal": rehearsal}
    spec_path = os.path.join(entry, "build_spec.json")
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    t0 = time.monotonic()
    proc = subprocess.run([sys.executable, os.path.abspath(__file__), spec_path],
                          stdout=sys.stderr, check=False)
    if proc.returncode != 0 or not os.path.exists(meta_path):
        shutil.rmtree(entry, ignore_errors=True)
        raise RuntimeError(f"corpus build failed (rc {proc.returncode})")
    with open(meta_path) as f:
        meta = json.load(f)
    return entry, {**meta, "cache": "miss",
                   "child_s": time.monotonic() - t0}


def build(spec: dict) -> dict:
    """The child: build the shard and leave it as a backup tree."""
    import jax
    import numpy as np

    from benchmark import data
    from vearch_tpu.cluster.objectstore import LocalObjectStore
    from vearch_tpu.engine.engine import Engine
    from vearch_tpu.engine.types import IndexStatus, TableSchema
    from vearch_tpu.utils import enable_compilation_cache

    cfg, seed, rows, entry = (spec["config"], spec["seed"], spec["rows"],
                              spec["entry"])
    enable_compilation_cache()
    platform = jax.devices()[0].platform
    if platform != "tpu" and not (spec["rehearsal"] and platform == "cpu"):
        raise SystemExit(f"corpus build: no TPU (jax found {platform})")
    t = {"start": time.monotonic()}
    base, _, _ = data.make_data(cfg, seed, rows)
    t["data"] = time.monotonic()
    vec = cfg["vector_field"]
    cols = {c["name"]: data.scalar_column(c, rows)
            for c in cfg.get("scalar_columns", [])}
    eng = Engine(TableSchema.from_dict(table_schema_dict(cfg, rows)))
    for lo in range(0, rows, INGEST_BATCH):
        hi = min(lo + INGEST_BATCH, rows)
        eng.upsert([{"_id": f"doc{i}", vec: base[i],
                     **{n: float(c[i]) for n, c in cols.items()}}
                    for i in range(lo, hi)])
    t["upsert"] = time.monotonic()
    eng.wait_for_index(timeout=1500.0)
    if eng.status != IndexStatus.INDEXED or eng.last_build_error is not None:
        raise SystemExit(f"corpus build: index not built: status "
                         f"{eng.status!r}, error {eng.last_build_error!r}")
    t["index"] = time.monotonic()
    dump = os.path.join(entry, "dump")
    eng.dump(dump)
    doc_count = int(eng.doc_count)
    eng.close()
    t["dump"] = time.monotonic()
    space = cfg["space"]["name"]
    prefix = f"backup/{DB}/{space}"
    out = LocalObjectStore(os.path.join(entry, "store")).put_tree_dedup(
        f"{prefix}/v1/shard_0", dump, f"{prefix}/pool/shard_0")
    shutil.rmtree(dump)
    t["tree"] = time.monotonic()
    names = list(t)
    meta = {"rows": rows, "doc_count": doc_count, "files": out["files"],
            "build_job": {k: eng.build_job.get(k) for k in
                          ("status", "duration_seconds", "phases_ms")}
            if eng.build_job else None,
            "seconds": {b: t[b] - t[a] for a, b in zip(names, names[1:])}}
    with open(os.path.join(entry, "meta.json"), "w") as f:
        json.dump(meta, f)
    print(json.dumps({"corpus_build": meta}), file=sys.stderr, flush=True)
    return meta


if __name__ == "__main__":
    with open(sys.argv[1]) as fh:
        build(json.load(fh))
