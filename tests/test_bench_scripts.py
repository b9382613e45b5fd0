"""Per-index benchmark suite smoke (reference intent:
scripts/benchmarks/*.py are runnable against any build)."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_headline_bench_dryrun_pipeline():
    """VEARCH_BENCH_DRYRUN runs bench.py's FULL pipeline at toy scale on
    CPU — a bench-code regression must fail HERE, not in a chip run."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "VEARCH_BENCH_DRYRUN": "1"}, cwd=REPO,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["value"] > 0 and "error" not in line
    assert line["unit"] == "qps" and line["vs_baseline"] > 0


@pytest.mark.slow
def test_per_index_bench_runs_and_reports():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "benchmarks",
                                      "per_index.py"),
         "--n", "8000", "--d", "16", "--indexes", "FLAT,IVFFLAT",
         "--batches", "1,64"],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=REPO,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    rows = [json.loads(line) for line in out.stdout.splitlines()
            if line.startswith("{")]
    assert {(r["index"], r["batch"]) for r in rows} == {
        ("FLAT", 1), ("FLAT", 64), ("IVFFLAT", 1), ("IVFFLAT", 64)}
    for r in rows:
        assert r["qps"] > 0 and r["p50_ms"] > 0
        assert r["recall_at_10"] >= 0.8


@pytest.mark.slow
def test_restful_cluster_bench_runs_and_reports():
    """The cluster-path benchmark (r4 review next-4): REST rows through
    a live standalone cluster next to engine rows on the same data,
    plus an explicit router-overhead delta."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "benchmarks",
                                      "restful.py"),
         "--n", "5000", "--d", "16", "--nq", "8", "--indexes", "FLAT",
         "--batches", "1,32", "--partitions", "2", "--seconds", "0.5"],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=REPO,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    rows = [json.loads(line) for line in out.stdout.splitlines()
            if line.startswith("{")]
    paths = {(r["path"], r["batch"]) for r in rows}
    assert paths == {("engine", 1), ("engine", 32),
                     ("rest", 1), ("rest", 32),
                     ("delta", 1), ("delta", 32)}
    for r in rows:
        if r["path"] in ("engine", "rest"):
            assert r["qps"] > 0 and r["recall_at_10"] >= 0.9
        else:
            assert "router_overhead_ms_p50" in r
