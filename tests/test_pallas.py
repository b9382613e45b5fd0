"""Pallas probe-kernel tests: results, in interpret mode — which the
kernels choose on the `cpu` backend only. Mosaic compiles the same code
on the `tpu` backend; tests/test_chip_compile.py compiles it for a
described v5e, and `chip_smoke.py` runs it on the chip."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from vearch_tpu.engine.engine import Engine, SearchRequest
from vearch_tpu.engine.types import (
    DataType, FieldSchema, IndexParams, MetricType, TableSchema,
)
from vearch_tpu.ops.ivf import _coarse_probes, ivfpq_candidates
from vearch_tpu.ops import pallas_kernels
from vearch_tpu.ops.pallas_kernels import ivf_probe_dots, ivfpq_probe_search_pallas


def _setup(rng, nlist=16, cap=128, d=32):
    cents = rng.standard_normal((nlist, d)).astype(np.float32)
    resid8 = rng.integers(-127, 128, (nlist, cap, d)).astype(np.int8)
    scale = ((0.01 + rng.random(nlist)) * 0.01).astype(np.float32)
    ids = np.arange(nlist * cap).reshape(nlist, cap).astype(np.int32)
    approx = cents[:, None, :] + scale[:, None, None] * resid8.astype(np.float32)
    vsq = (approx ** 2).sum(-1).astype(np.float32)
    valid = np.ones(nlist * cap, bool)
    return cents, resid8, scale, ids, vsq, valid


def test_probe_dots_matches_einsum(rng):
    cents, resid8, scale, ids, vsq, valid = _setup(rng)
    q = rng.standard_normal((4, 32)).astype(np.float32)
    probes = jnp.asarray(rng.integers(0, 16, (4, 4)).astype(np.int32))
    out = np.asarray(ivf_probe_dots(jnp.asarray(q), probes, jnp.asarray(resid8)))
    qb = np.asarray(jnp.asarray(q, jnp.bfloat16), np.float32)
    ref = np.einsum("bd,bjcd->bjc", qb,
                    resid8[np.asarray(probes)].astype(np.float32))
    np.testing.assert_allclose(out, ref, rtol=1e-3, atol=1e-2)


def test_pallas_probe_search_matches_scan_kernel(rng):
    cents, resid8, scale, ids, vsq, valid = _setup(rng)
    q = rng.standard_normal((4, 32)).astype(np.float32)
    s1, i1 = ivfpq_probe_search_pallas(
        jnp.asarray(q), jnp.asarray(cents), jnp.asarray(resid8),
        jnp.asarray(scale), jnp.asarray(vsq), jnp.asarray(ids),
        jnp.asarray(valid), 4, 10)
    s2, i2 = ivfpq_candidates(
        jnp.asarray(q), jnp.asarray(cents), jnp.asarray(resid8),
        jnp.asarray(scale), jnp.asarray(vsq), jnp.asarray(ids),
        jnp.asarray(valid), 4, 10)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=1e-3, atol=1e-2)


def test_engine_probe_mode_uses_pallas(rng):
    centers = rng.standard_normal((30, 32)).astype(np.float32) * 4
    vecs = (centers[rng.integers(0, 30, 3000)]
            + 0.5 * rng.standard_normal((3000, 32)).astype(np.float32))
    schema = TableSchema("p", [FieldSchema(
        "v", DataType.VECTOR, dimension=32,
        index=IndexParams("IVFPQ", MetricType.L2,
                          {"ncentroids": 16, "nsubvector": 4,
                           "scan_mode": "probe", "nprobe": 16,
                           # force the pallas path even off-TPU (interpret
                           # mode) so the engine wiring is exercised here
                           "probe_kernel": "pallas",
                           "training_threshold": 500}))])
    eng = Engine(schema)
    eng.upsert([{"_id": f"d{i}", "v": vecs[i]} for i in range(3000)])
    eng.wait_for_index()
    eng.build_index()
    res = eng.search(SearchRequest(vectors={"v": vecs[:5]}, k=3))
    assert [r.items[0].key for r in res] == [f"d{i}" for i in range(5)]
    # explicit xla fallback kernel agrees
    res2 = eng.search(SearchRequest(vectors={"v": vecs[:5]}, k=3,
                                    index_params={"probe_kernel": "xla"}))
    assert [r.items[0].key for r in res2] == [f"d{i}" for i in range(5)]


@pytest.mark.parametrize("backend,want", [
    ("tpu", False), ("cpu", True), ("gpu", RuntimeError),
    ("some_plugin", RuntimeError)])
def test_interpret_mode_only_on_cpu_backend(monkeypatch, backend, want):
    """Compiled on the chip, interpreted on the CPU test backend, and an
    error anywhere else — never a silent interpreter on a device path."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if want is RuntimeError:
        with pytest.raises(RuntimeError, match=backend):
            pallas_kernels._interpret()
    else:
        assert pallas_kernels._interpret() is want
