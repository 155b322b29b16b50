"""Operations and bytes of the surrogate's device work, from shapes alone.

`gnn_mp` is the fused message-passing kernel (`relu(A' @ (H @ Wn) + H @ Ws
+ b)` over a block of graphs); `forward_flops` is the useful work of the
two-stage GraphSAGE-mean surrogate for one configuration: real nodes only,
no padded graph rows or padded batch rows. A multiply-add counts as two
operations; bias adds, ReLUs, masks and readout pooling are counted
elementwise.
"""
from __future__ import annotations

from typing import List, Tuple

F32 = 4


def gnn_mp(batch: int, nodes: int, feat: int, out: int,
           itemsize: int = F32) -> Tuple[int, int]:
    """(operations, bytes) of one `gnn_mp` call at its padded shapes: two
    (B*N, F) x (F, Fo) products, the (N, N) x (N, Fo) aggregation per
    graph, then the sum, bias and ReLU. Bytes: adjacency, features, both
    weight panels and the bias read once, the output written once."""
    flops = (2 * 2 * batch * nodes * feat * out
             + 2 * batch * nodes * nodes * out
             + 3 * batch * nodes * out)
    data = itemsize * (batch * nodes * nodes + batch * nodes * feat
                       + 2 * feat * out + out + batch * nodes * out)
    return flops, data


def engine_chunk_calls(batch: int, n_pad: int, feat: int, hidden: int,
                       layers: int) -> List[Tuple[int, int]]:
    """(operations, bytes) of every `gnn_mp` call in one engine chunk:
    both stages, `layers` message-passing layers each."""
    calls = []
    for _ in range(2):
        d = feat
        for _ in range(layers):
            calls.append(gnn_mp(batch, n_pad, d, hidden))
            d = hidden
    return calls


def _stack(nodes: int, feat: int, hidden: int, layers: int) -> int:
    ops, d = 0, feat
    for _ in range(layers):
        ops += 2 * nodes * nodes * d               # adjacency @ h
        ops += nodes * d                            # divide by degree
        ops += 2 * 2 * nodes * d * hidden           # h @ Ws, mean @ Wn
        ops += 3 * nodes * hidden                   # two adds, bias, relu
        d = hidden
    return ops


def forward_flops(nodes: int, feat: int, hidden: int, layers: int,
                  targets: int = 4) -> int:
    """Useful operations of one configuration's two-stage forward:
    stage 1 (node-level crit logits) then stage 2 (graph-level targets)."""
    stage1 = (_stack(nodes, feat, hidden, layers)
              + 2 * nodes * hidden * hidden + 2 * nodes * hidden   # head 1
              + 2 * nodes * hidden + nodes)                         # head 2
    stage2 = (_stack(nodes, feat, hidden, layers)
              + 2 * nodes * hidden                                  # pooling
              + 2 * 2 * hidden * hidden + 2 * hidden                # head 1
              + 2 * hidden * targets + targets)                     # head 2
    return stage1 + stage2
