"""Operations and bytes each model and kernel needs, from shapes alone.

These are what the algorithm requires, not what a program happens to run:
recomputed operations do not count, and a causal kernel is charged the
causal half. Roofline shares and MFU are computed from them, and a share
above 100% means one of these counts is too high or the time leaves work
out, so :func:`share_pct` raises.
"""
from __future__ import annotations


def gpt_matmul_params(cfg: dict) -> int:
    """Parameters that sit in a matrix multiplication of the forward pass:
    the blocks' projections and the tied output head (not the look-ups)."""
    h, f = cfg["n_embd"], cfg["n_inner"]
    return cfg["n_layer"] * (4 * h * h + 2 * h * f) + cfg["vocab_size"] * h


def gpt_train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward of one token at sequence length ``seq_len``:
    6 per matmul parameter, plus causal attention (QK^T and PV, forward 1x
    and backward 2x, each 2*S*h multiply-adds halved by the mask)."""
    return (6.0 * gpt_matmul_params(cfg)
            + 6.0 * cfg["n_layer"] * seq_len * cfg["n_embd"])


def flash_call_cost(kernel: str, batch: int, heads: int, seq: int,
                    head_dim: int, itemsize: int) -> dict:
    """One call of a causal flash kernel over ``[batch, seq, heads, D]``.
    Matmuls of ``2*S*S*D`` operations per head, halved by the mask:
    forward 2 (QK^T, PV); dQ 3 (QK^T, dO V^T, dS K); dK/dV 4 (QK^T,
    dO V^T, P^T dO, dS^T Q). Bytes: each operand and result once."""
    matmuls = {"flash_fwd": 2, "flash_bwd_dq": 3, "flash_bwd_dkv": 4}[kernel]
    tensors = {"flash_fwd": 4, "flash_bwd_dq": 6, "flash_bwd_dkv": 7}[kernel]
    flops = matmuls * 2.0 * batch * heads * seq * seq * head_dim / 2.0
    nbytes = tensors * batch * seq * heads * head_dim * itemsize
    return {"flops": flops, "bytes": float(nbytes)}


def paged_attn_cost(kv_rows: float, heads: int, head_dim: int,
                    itemsize: int, queries: float) -> dict:
    """Paged decode attention of one layer over ``kv_rows`` cached rows in
    all (summed over the sequences of the ticks counted) for ``queries``
    one-token queries: QK^T and PV are 2*D operations per row and head
    each; every K and V row is read once, q read and o written once."""
    flops = 4.0 * kv_rows * heads * head_dim
    nbytes = (2.0 * kv_rows + 2.0 * queries) * heads * head_dim * itemsize
    return {"flops": flops, "bytes": nbytes}


def least_seconds(cost: dict, peaks: dict) -> tuple:
    """The least time the chip could take, and which peak bounds it."""
    t_flops = cost["flops"] / peaks["flops_per_s"]
    t_bytes = cost["bytes"] / peaks["bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")


def share_pct(least_s: float, measured_s: float, what: str) -> float:
    """``least_s`` as a share of ``measured_s``; above 100% is a bug in a
    count or a time, and raises instead of being clipped."""
    if measured_s <= 0:
        raise ValueError(f"{what}: measured time {measured_s} is not positive")
    pct = 100.0 * least_s / measured_s
    if pct > 100.0:
        raise ValueError(
            f"{what}: {pct:.1f}% of its peak — the operations or bytes are "
            f"counted too high, or the time leaves out part of the work")
    return pct
