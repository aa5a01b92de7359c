"""Operations and bytes the flash-attention calls of one training step
need, from shapes alone. The yardstick of ``flash_attn_roofline``.

Causal attention over S positions visits half of the S x S score
matrix. Per layer, with B rows, H heads of width D:

- forward: two matrix products (Q K^T and P V), 2 * 2*B*H*S*S*D / 2
  = 2*B*H*S^2*D operations; reads Q, K, V and writes O;
- backward: five (S = Q K^T again, dP = dO V^T, dV = P^T dO,
  dQ = dS K, dK = dS^T Q) = 5*B*H*S^2*D; reads Q, K, V, O, dO and
  writes dQ, dK, dV.

Full recomputation runs the forward twice (once in the forward pass,
once again inside the backward pass), so a step makes two forward
calls and one backward call per layer. What a kernel recomputes
inside itself beyond these products (a backward split in two passes
recomputes S and dP) is not needed by the algorithm and not counted:
it lowers the share, as it should.
"""
from __future__ import annotations


def flash_step_cost(*, batch: int, heads: int, seq: int, head_dim: int,
                    layers: int, forward_calls: int, elem_bytes: int = 2
                    ) -> dict:
    """FLOPs and HBM bytes of all flash calls of one training step."""
    unit = batch * heads * seq * seq * head_dim
    fwd_flops = 2 * unit
    bwd_flops = 5 * unit
    tensor = batch * seq * heads * head_dim * elem_bytes
    lse = batch * heads * seq * 4
    fwd_bytes = 4 * tensor + lse            # Q K V in, O and lse out
    bwd_bytes = 8 * tensor + 2 * lse        # Q K V O dO in, dQ dK dV out
    return {
        "flops": layers * (forward_calls * fwd_flops + bwd_flops),
        "bytes": layers * (forward_calls * fwd_bytes + bwd_bytes),
    }


def roofline(cost: dict, peaks: dict) -> dict:
    """The least time the chip could take for ``cost`` and which of
    the two bounds it."""
    t_flops = cost["flops"] / peaks["bf16_flops_per_s"]
    t_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return {"min_seconds": max(t_flops, t_bytes),
            "bound": "compute" if t_flops >= t_bytes else "memory",
            "t_flops": t_flops, "t_bytes": t_bytes}


def train_flops_per_token(*, n_params: int, layers: int, hidden: int,
                          seq: int) -> float:
    """6N + 12*L*H*S (bench.py's arithmetic): forward and backward of
    the matrix products, plus attention. Recomputed operations are not
    counted: recomputation earns nothing."""
    return 6.0 * n_params + 12.0 * layers * hidden * seq
