"""Kernels, IVF probe regime: of the serving program's device time
inside the traced window, the share spent selecting: `sort` / `top_k`
operations by the operation's own name (`%sort.8 = ...`; a `TopK`
custom call by its target). In `ivfflat_candidates` that is one fold
of [B, r + tile] scores a scan step (`nprobe` x tiles of them a
dispatch; the chip's compiler lowers each `top_k` to a full sort) and
the coarse selection's one [B, nlist] sort. The fold's
`take_along_axis` is a gather and is read by `ivf_gather_pct`. A
program that is not on the trace under the kernel's module name reads
nothing."""

from benchmark.metrics.ivf_gather_pct import head, share_pct


def is_selection(op_name: str) -> bool:
    own = head(op_name).lower()
    return ("sort" in own or "top_k" in own or "topk" in own
            or 'custom_call_target="TopK"' in op_name)


def read(obs):
    return share_pct(obs, is_selection)
