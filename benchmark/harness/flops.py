"""Operations and bytes from shapes: the arithmetic every utilisation and
roofline share in this benchmark rests on.

Conventions, the same for every configuration:

* A multiply-add is two operations.  Backward is twice forward, so a
  training step needs three times the forward operations.
* Only the operations the model requires count: nothing recomputed
  (rematerialised blocks, the score tile the flash backward rebuilds).
* Causal attention is counted as the half of the score matrix it needs.
* Embedding lookups, layer norms, softmax, activations and the optimizer
  are not counted: matrix multiplications only.
"""

from __future__ import annotations

from typing import Any, Dict


def transformer_train_flops_per_token(shape: Dict[str, Any]) -> float:
    """Model operations per trained token, forward and backward.

    ``shape``: n_layer, d_model, d_ff, seq_len, vocab_size, causal.  Every
    position goes through the vocabulary head.
    """
    d, ff = int(shape["d_model"]), int(shape["d_ff"])
    t, v = int(shape["seq_len"]), int(shape["vocab_size"])
    layers = int(shape["n_layer"])
    proj = 2 * (4 * d * d + 2 * d * ff)               # qkv, out, fc1, fc2
    attn = 4 * t * d * (0.5 if shape["causal"] else 1.0)   # QK^T and PV
    forward = layers * (proj + attn) + 2 * d * v
    return 3.0 * forward


def mfu_pct(tokens_per_s_per_chip: float, flops_per_token: float,
            peak_flops_per_s: float) -> float:
    return 100.0 * tokens_per_s_per_chip * flops_per_token / peak_flops_per_s


# -- the flash attention kernels ----------------------------------------------
#
# One call covers ``rows`` (batch x heads on this chip) score matrices of
# seq x seq with head size ``head_dim``.  Operands are bf16 (2 bytes);
# the log-sum-exp and delta rows are f32.

_ACT = 2   # bytes of a q/k/v/o/grad element
_ROW = 4   # bytes of an lse/delta element


def _tile_share(causal: bool) -> float:
    return 0.5 if causal else 1.0


def flash_call_cost(kind: str, *, rows: int, seq: int, head_dim: int,
                    causal: bool) -> Dict[str, float]:
    """FLOPs and HBM bytes one kernel call needs.  ``kind``: ``fwd``
    (QK^T, PV), ``dq`` (dP = dO V^T, dQ = dS K) or ``dkv`` (dV = P^T dO,
    dK = dS^T Q).  The score tile each backward kernel rebuilds is
    recomputation and is left out, so a share read from these never
    flatters the kernel."""
    mat = 2.0 * seq * seq * head_dim * _tile_share(causal) * rows
    tensor = rows * seq * head_dim * _ACT
    row = rows * seq * _ROW
    if kind == "fwd":
        return {"flops": 2 * mat, "bytes": 4 * tensor + row}
    if kind == "dq":      # reads q k v do, lse delta; writes dq
        return {"flops": 2 * mat, "bytes": 5 * tensor + 2 * row}
    if kind == "dkv":     # reads q k v do, lse delta; writes dk dv
        return {"flops": 2 * mat, "bytes": 6 * tensor + 2 * row}
    raise ValueError(f"unknown flash kernel kind {kind!r}")


def least_seconds(cost: Dict[str, float], peaks: Dict[str, Any]) -> Dict[str, Any]:
    """The roofline: the larger of operations over peak FLOP/s and bytes
    over peak bytes/s, and which of the two it is."""
    by_flops = cost["flops"] / float(peaks["bf16_flops_per_s"])
    by_bytes = cost["bytes"] / float(peaks["hbm_bytes_per_s"])
    return {"seconds": max(by_flops, by_bytes),
            "bound": "compute" if by_flops >= by_bytes else "memory"}
