"""Kernels, three-stage refinement: of the serving program's device time
inside the traced window, the share spent selecting: `sort` / `top_k` /
`TopK` operations by the operation's own name (`ivf_fold_topk_pct`'s
rule). In `binary_refine_rerank` that is stage 0's selection over the
block maxima and over the `r0` x 128 gathered scores a query (the
chip's compiler lowers each `top_k` to full sorts), stage 1's top `r1`
of `r0` and stage 2's top k of `r1`. A program that is not on the trace
under the kernel's module name reads nothing."""

from benchmark.metrics.ivf_fold_topk_pct import is_selection
from benchmark.metrics.ivf_gather_pct import share_pct


def read(obs):
    return share_pct(obs, is_selection)
