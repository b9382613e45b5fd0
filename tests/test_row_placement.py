"""The raw store and the int8 mirror as placed for a row GATHER on one
chip (`RawVectorStore.device_buffer(packed=True)`,
`Int8Mirror.flush(packed=True)`, `ops/ivf.py` `gather_rows`): at a
width that is no multiple of 128 the chip lays `[n, d]` out
column-major and a row gather copies it whole, so the programs that
gather rows take `[n / pack, pack * d]` super-rows (PERF.md section 6,
PR 34). The placement must hold the same rows, append the same tails
and re-place on the same occasions as the plain one; at `pack` 1 it IS
the plain one.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vearch_tpu.engine.raw_vector import RawVectorStore
from vearch_tpu.engine.types import MetricType
from vearch_tpu.index.int8_mirror import Int8Mirror, quantize_rows
from vearch_tpu.ops import binary_scan as binary_ops
from vearch_tpu.ops import ivf as ivf_ops
from vearch_tpu.parallel.mesh import row_pack

RNG = np.random.default_rng(34)


def rows_of(n, d):
    return RNG.standard_normal((n, d)).astype(np.float32)


def logical(placed, d):
    """[n, d] rows of a placement `[n / pack, pack * d]`."""
    return np.asarray(placed).reshape(-1, d)


@pytest.mark.parametrize("d,pack", [(960, 2), (96, 4), (128, 1), (768, 1),
                                    (100, 1)])
def test_store_placed_for_a_gather_holds_the_rows(d, pack):
    assert row_pack(d) == pack
    store = RawVectorStore(d, init_capacity=64)
    x = rows_of(51, d)  # an odd count: the last super-row is half full
    store.add(x)
    base, sqn, n = store.device_buffer(packed=True)
    assert n == 51 and base.shape == (64 // pack, pack * d)
    assert (logical(base, d)[:51] == x).all()
    assert (logical(base, d)[51:] == 0).all()
    plain, plain_sqn, _ = store.device_buffer()
    assert plain.shape == (64, d)
    assert (np.asarray(sqn) == np.asarray(plain_sqn)).all()  # bit for bit
    if pack == 1:
        assert base is plain and store._packed is None
    else:
        assert store._packed is base and store._device is plain
    # a tail that starts and ends inside super-rows
    y = rows_of(6, d)
    store.add(y)
    base2, sqn2, n2 = store.device_buffer(packed=True)
    assert n2 == 57 and base2.shape == base.shape
    assert (logical(base2, d)[:57] == np.concatenate([x, y])).all()
    plain2, plain_sqn2, _ = store.device_buffer()
    assert (np.asarray(sqn2) == np.asarray(plain_sqn2)).all()
    assert (logical(base2, d) == np.asarray(plain2)).all()
    # the capacity doubles: placed anew, whole
    store.add(rows_of(10, d))
    base3, sqn3, n3 = store.device_buffer(packed=True)
    assert store.capacity == 1024  # `add` grows to 1,024 rows at least
    assert n3 == 67 and base3.shape == (1024 // pack, pack * d)
    assert (logical(base3, d)[:67] == store.host_view()).all()
    assert np.asarray(sqn3).shape == (1024,)


def test_a_capacity_of_no_whole_super_rows_is_padded():
    """A restored store's capacity is exactly its row count."""
    d = 96
    store = RawVectorStore(d, init_capacity=64)
    store._host = rows_of(1027, d)
    store._n = 1027
    base, sqn, n = store.device_buffer(packed=True)
    assert base.shape == (257, 4 * d) and sqn.shape == (1027,) and n == 1027
    assert (logical(base, d)[:1027] == store.host_view()).all()
    assert (logical(base, d)[1027:] == 0).all()
    got = ivf_ops.gather_rows(base, jnp.asarray([0, 5, 1026]), d)
    assert (np.asarray(got) == store.host_view()[[0, 5, 1026]]).all()


def test_a_replacement_lets_the_old_buffer_go_first(monkeypatch):
    """The write check's finding at 1M x 960: a store that doubled was
    placed beside its predecessor (3.84 + 7.68 GB at the peak) and its
    norms were taken from a copy brought back down. Now the old buffer
    is released before the new one goes up, and a float32 store's norms
    come from the host rows."""
    import vearch_tpu.engine.raw_vector as rv

    store = RawVectorStore(960, init_capacity=8)
    store.add(rows_of(8, 960))
    first, _, _ = store.device_buffer(packed=True)
    store.add(rows_of(1, 960))  # doubles the capacity
    seen = {}
    real = rv.jnp.asarray

    def spy(a, *args, **kw):
        if getattr(a, "ndim", 0) == 2:
            seen["old_still_held"] = store._packed is not None
        return real(a, *args, **kw)

    def no_device_array(a, *args, **kw):
        if isinstance(a, jax.Array):
            raise AssertionError("a device buffer came back to the host")
        return np.array(a, *args, **kw)

    with monkeypatch.context() as patched:  # `rv.np` IS numpy: undo after
        patched.setattr(rv.jnp, "asarray", spy)
        patched.setattr(rv.np, "asarray", no_device_array)
        second, sqn, n = store.device_buffer(packed=True)
    assert seen == {"old_still_held": False}
    assert second.shape == (512, 1920) and n == 9 and first.shape == (4, 1920)
    want = (store.host_view().astype(np.float64) ** 2).sum(1)
    assert np.allclose(np.asarray(sqn)[:9], want, rtol=1e-6)


@pytest.mark.parametrize("storage,d,width,pack", [
    ("int8", 960, 960, 2), ("int8", 128, 128, 1), ("int4", 960, 480, 4),
    ("bits", 960, 120, 1)])
def test_mirror_placed_for_a_gather_holds_the_payload(storage, d, width,
                                                      pack):
    m = Int8Mirror(d, storage=storage)
    assert m._row_width == width and row_pack(width) == pack
    x = rows_of(1001, d)
    m.append(x)
    p8, scale, vsq = m.flush(packed=True)
    cap = m._h8.shape[0]
    assert cap % 512 == 0 and p8.shape == (cap // pack, pack * width)
    assert (logical(p8, width) == m._h8).all()
    if pack == 1:
        assert p8 is m._d8 and m._dp8 is None
    else:  # only the form that was asked for is placed
        assert p8 is m._dp8 and m._d8 is None
    l8, l_scale, l_vsq = m.flush()
    assert l8.shape == (cap, width) and (np.asarray(l8) == m._h8).all()
    assert l_scale is scale and l_vsq is vsq  # the columns are shared
    # a tail from an odd row on; then rows below the mark written again
    y = rows_of(6, d)
    m.append(y)
    p8b, scale_b, _ = m.flush(packed=True)
    assert (logical(p8b, width) == m._h8).all()
    assert (np.asarray(scale_b) == m._h_scale).all()
    m.append(rows_of(3, d), start=500)
    p8c, scale_c, vsq_c = m.flush(packed=True)
    assert (logical(p8c, width) == m._h8).all()
    assert (np.asarray(scale_c) == m._h_scale).all()
    assert (np.asarray(vsq_c) == m._h_vsq).all()
    assert (np.asarray(m.flush()[0]) == m._h8).all()


def test_rerank_and_rescore_read_a_packed_store_as_a_plain_one():
    """`exact_rerank` and stage 1 of the three-stage chain give the same
    answer from `[n / 2, 1920]` super-rows as from `[n, 960]` rows; at
    pack 1 `gather_rows` is the plain gather."""
    n, d, b = 1024, 960, 8
    x = rows_of(n, d)
    q = x[:b] + 0.01 * rows_of(b, d)
    cand = jnp.asarray(RNG.integers(-1, n, (b, 64)), jnp.int32)
    sqn = jnp.asarray((x.astype(np.float64) ** 2).sum(1), jnp.float32)
    plain = ivf_ops.exact_rerank(jnp.asarray(q), cand, jnp.asarray(x), sqn,
                                 10, MetricType.L2)
    packed = ivf_ops.exact_rerank(jnp.asarray(q), cand,
                                  jnp.asarray(x.reshape(n // 2, 2 * d)), sqn,
                                  10, MetricType.L2)
    assert (np.asarray(plain[1]) == np.asarray(packed[1])).all()
    assert (np.asarray(plain[0]) == np.asarray(packed[0])).all()
    q8, scale, vsq = quantize_rows(x)
    args = (jnp.asarray(scale), jnp.asarray(vsq), 32, MetricType.L2, "int8")
    s_plain = binary_ops._mirror_rescore(jnp.asarray(q), cand,
                                         jnp.asarray(q8), *args)
    s_packed = binary_ops._mirror_rescore(
        jnp.asarray(q), cand, jnp.asarray(q8.reshape(n // 2, 2 * d)), *args)
    assert (np.asarray(s_plain[1]) == np.asarray(s_packed[1])).all()
    assert np.allclose(np.asarray(s_plain[0]), np.asarray(s_packed[0]),
                       rtol=1e-6)
    rows = jnp.asarray([[3, 4], [1023, 0]])
    assert (np.asarray(ivf_ops.gather_rows(jnp.asarray(x), rows, d))
            == x[np.asarray(rows)]).all()


def test_an_absorb_in_pieces_keeps_the_capacity_of_one_append():
    """IVFRABITQ quantises 4,096 rows at a time; without `reserve` the
    mirrors doubled their way to the next power of two above the corpus
    (1,048,576 for 1,000,000 rows: every scan 4.8 % longer, found on the
    chip: PERF.md section 6, PR 34)."""
    from vearch_tpu.engine.types import IndexParams
    from vearch_tpu.index import binary

    n, d = 3 * binary.ABSORB_ROWS + 17, 64
    x = rows_of(n, d)
    store = RawVectorStore(d)
    store.add(x)
    index = binary.IVFRaBitQIndex(
        IndexParams("IVFRABITQ", MetricType.L2, {"ncentroids": 8}), store)
    index.train(x)
    index.absorb(n)
    want = -(-n // 512) * 512
    assert index._mirror._h8.shape == (want, d)
    assert index._bits._h8.shape == (want, d // 8)
    whole = Int8Mirror(d)
    whole.append(x)
    assert whole._h8.shape[0] == want
    # and the pieces hold what one pass over all rows would
    cents = np.asarray(index.centroids)
    assign = np.concatenate([np.full(len(m), c) for c, m in
                             enumerate(index._members)])
    order = np.concatenate([np.asarray(m, int) for m in index._members])
    lists = np.empty(n, int)
    lists[order] = assign
    resid = x - cents[lists]
    recon = cents[lists] + np.maximum(
        np.abs(resid).mean(1), 1e-12).astype(np.float32)[:, None] * np.sign(
        resid)
    q8, scale, vsq = quantize_rows(recon.astype(np.float32))
    assert (index._mirror._h8[:n] == q8).all()
    assert (index._mirror._h_scale[:n] == scale).all()
    assert (index._mirror._h_vsq[:n] == vsq).all()
