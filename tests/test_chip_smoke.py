"""chip_smoke.py rehearsed on the CPU backend, and the compile-cache rule.

Every run is a child process with an environment of its own: the smoke
owns its process (it exits through sys.exit, turns the persistent
compilation cache on, and on one device must not see the suite's eight).
No child describes the TPU topology — only tests/test_chip_compile.py
loads the TPU library. A rehearsal proves paths and answers, never speed.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")
REHEARSAL_RC = 4
ROWS = 20_000
SMALL = ["--rows", str(ROWS), "--ncentroids", "64"]


def _env(tmp_path, devices: int) -> dict:
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "PYTHONPATH", "JAX_COMPILATION_CACHE_DIR")}
    env["JAX_PLATFORMS"] = "cpu"
    if devices > 1:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    # CPU programs stay out of the checkout's cache directory
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    return env


def _run(args, env, cwd=REPO, script=SMOKE):
    # faulthandler: if the child ever aborts, its threads' stacks are in
    # the stderr the failing assertion prints
    return subprocess.run([sys.executable, "-X", "faulthandler", script, *args], env=env, cwd=cwd,
                          capture_output=True, text=True, timeout=600)


def _lines(out):
    assert out.returncode == REHEARSAL_RC, out.stderr[-3000:]
    lines = [json.loads(ln) for ln in out.stdout.splitlines()]
    return lines[:-1], lines[-1]


@pytest.fixture(scope="module")
def one_chip_rehearsal(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("smoke1")
    return _run(["--rehearse-cpu", *SMALL], _env(tmp, 1)), tmp


def test_rehearsal_runs_every_phase_in_order(one_chip_rehearsal):
    earlier, last = _lines(one_chip_rehearsal[0])
    assert last == {"ok": False, "device": {
        "platform": "cpu", "kind": "cpu", "count": 1}}
    assert [ln["phase"] for ln in earlier] == [
        "device", "data", "ingest", "build", "request", "request",
        "request", "request", "request", "request", "compiled_programs",
        "write_read_delete", "shadow_sampler", "memory", "compile_cache"]
    dev, data, ingest, build = earlier[:4]
    assert dev["platform"] == "cpu" and dev["rehearsal"] is True
    assert dev["jax"] and dev["jaxlib"] and "native_helpers" in dev
    assert data["rows"] == ROWS and data["rows_cut_from_default"] is True
    assert ingest["rows"] == ROWS and ingest["seconds"] > 0
    assert build == {**build, "status": "done", "error": None,
                     "partition_status": "INDEXED", "docs_done": ROWS}
    assert build["phases_ms"]["train"] > 0 and build["phases_ms"]["assign"] > 0
    assert not os.path.exists(os.path.join(REPO, ".chip_smoke_data"))


def test_rehearsal_requests_name_their_paths_and_meet_recall(
        one_chip_rehearsal):
    earlier, _ = _lines(one_chip_rehearsal[0])
    reqs = {ln["kind"]: ln for ln in earlier if ln["phase"] == "request"}
    assert list(reqs) == ["single", "batch64", "batch64_rerank128",
                          "batch64_filtered", "batch64_probe_pallas",
                          "batch64_probe_xla"]
    assert reqs["batch64"]["rerank"] == 256
    assert reqs["batch64_rerank128"]["rerank"] == 128
    for kind in ("single", "batch64", "batch64_rerank128",
                 "batch64_filtered"):
        assert reqs[kind]["dispatches"] == ["fused_scan_rerank"]
        assert reqs[kind]["perf_path"] == "ivfpq_full_fused"
    for kind, kernel in (("batch64_probe_pallas", "pallas"),
                         ("batch64_probe_xla", "xla")):
        assert reqs[kind]["dispatches"] == ["probe_scan", "rerank"]
        assert reqs[kind]["kernels"] == {"probe_scan": kernel}
        assert reqs[kind]["recall_at_10"] >= 0.80
    assert reqs["batch64"]["recall_at_10"] >= 0.95
    assert reqs["batch64_filtered"]["recall_at_10"] >= 0.95
    assert reqs["batch64_filtered"]["filter_pass_fraction"] == 0.6
    assert reqs["batch64_probe_xla"]["pallas_xla_id_agreement"] >= 0.99
    for r in reqs.values():
        assert r["first_call_s"] > 0 and len(r["warmed_ms"]) == 5
    done = {ln["phase"]: ln for ln in earlier}
    assert done["write_read_delete"] == {
        "phase": "write_read_delete", "upsert_read_back": True,
        "found_by_search": True, "gone_after_delete": True}
    programs = done["compiled_programs"]["by_program"]
    # row buckets 8 and 64 at rerank 256, bucket 64 at rerank 128
    assert programs["ivf.int8_scan_rerank"] == 3
    assert programs["pallas.ivfpq_probe_search"] == 1


def test_second_run_in_the_same_place_reports_cache_hits(one_chip_rehearsal):
    first, tmp = one_chip_rehearsal
    earlier, _ = _lines(first)
    cold = earlier[-1]
    assert cold["phase"] == "compile_cache"
    assert cold["dir"] == str(tmp / "cache")
    assert cold["fresh_compiles"] > 0 and cold["entries_at_end"] > 0
    earlier, _ = _lines(_run(["--rehearse-cpu", *SMALL], _env(tmp, 1)))
    warm = earlier[-1]
    assert warm["entries_at_start"] == cold["entries_at_end"]
    assert warm["persistent_hits"] >= cold["fresh_compiles"]
    assert warm["fresh_compiles"] < cold["fresh_compiles"]


def test_four_chip_option_runs_only_the_mesh_phase(tmp_path):
    earlier, last = _lines(
        _run(["--rehearse-cpu", "--chips", "4", *SMALL], _env(tmp_path, 4)))
    assert last == {"ok": False, "device": {
        "platform": "cpu", "kind": "cpu", "count": 4}}
    assert [ln["phase"] for ln in earlier] == [
        "device", "data", "ingest", "build", "request", "mesh_residency",
        "request", "shadow_sampler", "memory", "compile_cache"]
    mesh, res, single = earlier[4:7]
    assert mesh["kind"] == "batch64_mesh"
    assert mesh["dispatches"] == ["sharded_fused_scan_rerank"]
    assert mesh["program"] == ["jit_sharded_fused_scan_rerank"]
    assert len(mesh["launch_us"]) == 6 and min(mesh["launch_us"]) > 0
    assert single["kind"] == "batch64_single_device"
    assert single["dispatches"] == ["fused_scan_rerank"]
    assert min(mesh["recall_at_10"], single["recall_at_10"]) >= 0.95
    assert single["mesh_single_id_agreement"] >= 0.99
    assert res["devices"] == 4 and res["data_shards"] == 4
    assert len(res["sharded_bytes"]) == 4
    assert all(0.2 <= s <= 0.3 for s in res["sharded_share"].values())
    assert min(res["sharded_bytes"].values()) >= 0.95 * res["placed_bytes"] / 4


@pytest.mark.parametrize("args,devices,says", [
    ([], 1, "no TPU"),                     # as the driver runs it
    (["--chips", "4"], 1, "no TPU"),
    (["--rehearse-cpu", "--chips", "4", *SMALL], 1, "jax sees 1 devices"),
    (["--rehearse-cpu", *SMALL], 4, "jax sees 4 devices"),
])
def test_refuses_without_the_devices_it_was_asked_for(
        tmp_path, args, devices, says):
    out = _run(args, _env(tmp_path, devices))
    assert out.returncode == 1
    assert out.stdout == ""  # no result, no "ok" line
    assert says in out.stderr


def test_fails_alone_in_a_directory(tmp_path):
    """A directory that holds chip_smoke.py and nothing else of the repo."""
    alone = tmp_path / "alone"
    alone.mkdir()
    shutil.copy(SMOKE, alone)
    out = _run([], _env(tmp_path, 1), cwd=str(alone),
               script=str(alone / "chip_smoke.py"))
    assert out.returncode not in (0, REHEARSAL_RC)
    assert out.stdout == ""


_CACHE_PROBE = """
import jax
from vearch_tpu import utils
first = utils.enable_compilation_cache()
second = utils.enable_compilation_cache()
print(first == second, first, jax.config.jax_compilation_cache_dir,
      jax.config.jax_persistent_cache_min_compile_time_secs)
"""


@pytest.mark.parametrize("placed_from_outside", [True, False])
def test_compile_cache_directory_rule(tmp_path, placed_from_outside):
    """JAX_COMPILATION_CACHE_DIR set: the program sets no directory in
    code and uses that one. Unset: ONE fixed path inside the checkout,
    the same across calls and across processes."""
    env = _env(tmp_path, 1)
    outside = env.pop("JAX_COMPILATION_CACHE_DIR")
    fixed = os.path.join(REPO, ".jax_cache")
    if placed_from_outside:
        env["JAX_COMPILATION_CACHE_DIR"] = outside
    seen = []
    for _ in range(2):  # two processes
        out = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env,
                             cwd=REPO, capture_output=True, text=True,
                             timeout=120)
        assert out.returncode == 0, out.stderr[-2000:]
        seen.append(out.stdout.split())
    assert seen[0] == seen[1]
    same, returned, configured, min_secs = seen[0]
    assert same == "True" and float(min_secs) == 0.0
    assert returned == configured == (outside if placed_from_outside
                                      else fixed)
