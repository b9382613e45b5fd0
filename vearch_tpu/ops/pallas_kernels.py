"""Pallas TPU kernels for the hot data-dependent ops.

The jit'd XLA paths in ops/ivf.py cover the dense-scan regimes; what XLA
cannot do well is *data-dependent* block movement — e.g. the IVF probe
scan, where each (query, probe-rank) step needs a different bucket row
from HBM. XLA lowers that to a batched gather + batched matvec that
materialises [B, cap, d] per probe step (measured 905 ms / 256-query
batch at SIFT1M scale). The Pallas kernel here instead uses
`PrefetchScalarGridSpec`: the probe table is scalar-prefetched, the
bucket block index_map reads it to DMA exactly the probed bucket into
VMEM (double-buffered across grid steps by the pallas pipeline), and the
MXU scores it — one pass over exactly the probed data.

Compiled by Mosaic on the `tpu` backend; interpret mode on the `cpu`
backend only (the test mesh), so the same code path is exercised there.
Any other backend is an error: a kernel that silently interprets where
it was meant to compile hides exactly the faults the compile exists to
find (tests/test_chip_compile.py compiles it for a described v5e).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from vearch_tpu.ops.perf_model import register_jit


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"pallas TPU kernels run compiled on 'tpu' or interpreted on "
        f"'cpu'; backend is {backend!r}"
    )


def _probe_dots_kernel(probes_ref, q_ref, bucket_ref, out_ref):
    """One grid step (i=query, j=probe rank): score query i against its
    j-th probed bucket.

    probes_ref: scalar-prefetched [B, nprobe] i32 (consumed by the
    index_maps; unused in the body). q_ref: [1, 1, d] (query i's row);
    bucket_ref: [1, cap, d] int8 (the DMA'd probed bucket);
    out_ref: [1, nprobe, cap] f32 (query i's output row, persistent across
    the inner j steps).
    """
    j = pl.program_id(1)
    q = q_ref[0]  # [1, d] bf16
    bucket = bucket_ref[0]  # [cap, d] int8
    dots = jax.lax.dot_general(
        q, bucket.astype(jnp.bfloat16),
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [1, cap]
    out_ref[0, pl.ds(j, 1), :] = dots


@functools.partial(jax.jit, static_argnames=())
def ivf_probe_dots(
    queries: jax.Array,        # [B, d] bf16/f32
    probes: jax.Array,         # [B, nprobe] i32
    bucket_resid8: jax.Array,  # [nlist, cap, d] int8
) -> jax.Array:
    """Raw dot products q . resid8 for every probed bucket: [B, nprobe, cap].

    Score assembly (dequant scale, centroid term, norms, masking, top-k)
    stays in XLA — it's elementwise over the output and fuses fine; the
    kernel exists purely to make the data-dependent bucket reads
    pipeline-DMA instead of a materialised gather.
    """
    b, d = queries.shape
    nprobe = probes.shape[1]
    nlist, cap, _ = bucket_resid8.shape
    qb = queries.astype(jnp.bfloat16)[:, None, :]  # [B, 1, d]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, nprobe),
        in_specs=[
            # query i's row; (1, 1, d) keeps Mosaic's tile alignment happy
            pl.BlockSpec((1, 1, d), lambda i, j, probes_ref: (i, 0, 0)),
            # data-dependent block: DMA the bucket this (query, rank)
            # step probes — the whole point of the scalar prefetch
            pl.BlockSpec(
                (1, cap, d),
                lambda i, j, probes_ref: (probes_ref[i, j], 0, 0),
            ),
        ],
        # one output row per query, persistent across the inner j loop.
        # A slimmer (1, 1, cap) per-step block does not compile: Mosaic
        # requires the second-to-last block dim to divide 8 or equal the
        # array dim, and this nprobe-row block is the smallest legal one.
        out_specs=pl.BlockSpec(
            (1, nprobe, cap), lambda i, j, probes_ref: (i, 0, 0)
        ),
    )
    return pl.pallas_call(
        _probe_dots_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, nprobe, cap), jnp.float32),
        interpret=_interpret(),
    )(probes, qb, bucket_resid8)


@functools.partial(jax.jit, static_argnames=("nprobe", "r", "l2"))
def ivfpq_probe_search_pallas(
    queries: jax.Array,        # [B, d] f32
    centroids: jax.Array,      # [nlist, d] f32
    bucket_resid8: jax.Array,  # [nlist, cap, d] int8
    bucket_scale: jax.Array,   # [nlist] f32
    bucket_vsq: jax.Array,     # [nlist, cap] f32
    bucket_ids: jax.Array,     # [nlist, cap] i32
    valid: jax.Array,          # [n_pad] bool
    nprobe: int,
    r: int,
    l2: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Full probe-mode IVFPQ search: coarse probe selection + pallas bucket
    scoring + top-k, one jitted program.

    The [B, nlist] query-centroid dot matrix is computed once and reused
    for both probe selection and the q.cent_c score term.

    Score decomposition per probed cluster c (approx v = cent_c + s_c*r8):
        q.v = q.cent_c + s_c * (q.r8);  L2 = -(|q|^2 - 2 q.v + |v|^2)
    """
    from vearch_tpu.ops.distance import sqnorms

    b, d = queries.shape
    qc = jax.lax.dot_general(
        queries, centroids, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )  # [B, nlist]
    # coarse assignment is L2 geometry (see ops/ivf.py _coarse_probes)
    coarse = 2.0 * qc - sqnorms(centroids)[None, :]
    _, probes = jax.lax.top_k(coarse, nprobe)  # [B, nprobe]
    dots8 = ivf_probe_dots(queries, probes, bucket_resid8)  # [B, np, cap]
    qc_p = jnp.take_along_axis(qc, probes, axis=1)  # [B, nprobe]
    scale_p = bucket_scale[probes]  # [B, nprobe]
    dots = qc_p[:, :, None] + scale_p[:, :, None] * dots8
    vsq_p = bucket_vsq[probes]  # [B, nprobe, cap]
    ids_p = bucket_ids[probes]  # [B, nprobe, cap]
    if l2:
        scores = -(sqnorms(queries)[:, None, None] - 2.0 * dots + vsq_p)
    else:
        scores = dots
    ok = (ids_p >= 0) & valid[jnp.maximum(ids_p, 0)]
    scores = jnp.where(ok, scores, -jnp.inf)
    flat_s = scores.reshape(b, nprobe * bucket_resid8.shape[1])
    flat_i = ids_p.reshape(b, nprobe * bucket_resid8.shape[1])
    r = min(r, flat_s.shape[1])
    top_s, pos = jax.lax.top_k(flat_s, r)
    return top_s, jnp.take_along_axis(flat_i, pos, axis=1)


# compiled-program tracking (ops/perf_model.py), as for the XLA search
# entry points in ops/ivf.py: the probe kernel's specialisations count
# in compiled_program_counts() and its compiles reach the flight recorder
ivfpq_probe_search_pallas = register_jit(
    "pallas.ivfpq_probe_search", ivfpq_probe_search_pallas
)
