"""Least work of one D-Krasulina round with gossip consensus, from shapes.

Per round every node reads its share of the samples once (the whole round
reads B x d float32 values), and each of the R consensus rounds reads and
writes the [N, d] float32 iterate-sized buffer once. The pseudo-gradient
takes two matrix-vector products per node: Z w and Z^T (Z w), 2 * B * d
FLOPs each over the round. The count is the same whether a fused kernel or
XLA computes the round."""

F32 = 4


def round_bytes(*, batch: int, dim: int, nodes: int, rounds: int) -> int:
    return batch * dim * F32 + rounds * 2 * nodes * dim * F32


def round_flops(*, batch: int, dim: int) -> int:
    return 2 * 2 * batch * dim


def least_seconds(*, batch: int, dim: int, nodes: int, rounds: int,
                  peaks: dict) -> float:
    """Roofline time of one round: the larger of the byte and FLOP bounds."""
    t_bytes = round_bytes(batch=batch, dim=dim, nodes=nodes,
                          rounds=rounds) / peaks["hbm_bytes_per_s"]
    t_flops = round_flops(batch=batch, dim=dim) / peaks["bf16_flops_per_s"]
    return max(t_bytes, t_flops)
