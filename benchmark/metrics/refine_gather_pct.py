"""Kernels, three-stage refinement: of the serving program's device time
inside the traced window, the share spent in gathers, by
`ivf_gather_pct`'s rule (an operation that is a `gather` or a
`dynamic-slice` by its own name, and the bare-named `%fusion.N` of
`kind=kCustom` the chip's compiler makes of every gather). In
`binary_refine_rerank` those are stage 1's `r0` int8 rows a query and
their two columns, stage 2's `r1` raw rows and their norms, stage 0's
gather of the selected score blocks and each stage's
`take_along_axis`. The sign-bit product with its fused unpack, the
block maxima and the loop fusions are the rest (`refine_select_pct`
reads the sorts). Only a cell that lists this metric reads it; a
program that is not on the trace under the kernel's module name reads
nothing."""

from benchmark.metrics.ivf_gather_pct import is_gather, share_pct


def read(obs):
    return share_pct(obs, is_gather)
