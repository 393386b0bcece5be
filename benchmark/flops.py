"""Operations and bytes the algorithms REQUIRE, computed from shapes, and
the table of peaks. Recomputed work (remat) and padding do not count: they
are the program's choices, not the model's.
"""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """The chip's published peaks. An unknown device is an error."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"benchmark/peaks.json")
    return table[device_kind]


def gpt2_matmul_params(model: dict) -> int:
    """Parameters that take part in a matrix multiplication: per block
    qkv (E x 3E), attention projection (E x E), MLP (E x 4E, 4E x E), and
    the output head (E x vocab, tied to the embedding). The embedding
    look-up and the position table are no matmul."""
    e, layers, vocab = model["n_embd"], model["n_layer"], model["vocab_size"]
    return layers * 12 * e * e + e * vocab


def gpt2_train_flops_per_token(model: dict, seq: int) -> int:
    """Forward and backward, no recompute: 6 per matmul parameter, plus
    attention's two T x T products per layer (QK^T and PV: 4*T*E forward
    per token, three times that with the backward pass = 12*L*T*E; the
    PaLM convention, not halved for causality)."""
    return 6 * gpt2_matmul_params(model) \
        + 12 * model["n_layer"] * seq * model["n_embd"]


def mfu_pct(flops_per_token: float, tokens_per_s_chip: float,
            device_kind: str) -> float:
    return 100.0 * flops_per_token * tokens_per_s_chip \
        / peaks(device_kind)["bf16_flops_per_s"]


# Flash attention, causal, per call over (B, H, T, D) in `itemsize` bytes.
# matmuls: T x T x D products the kernel must make (forward: QK^T, PV;
# dq: QK^T again, dO V^T, dS K; dkv: QK^T again, dO V^T, P^T dO, dS^T Q),
# each 2*T*T*D flops, halved because the causal half is never needed.
# tensors: (B, H, T, D) arrays the kernel must read or write once.
FLASH_KERNELS = {
    "fwd": {"matmuls": 2, "tensors": 4},   # q k v -> o
    "dq": {"matmuls": 3, "tensors": 5},    # q k v do -> dq
    "dkv": {"matmuls": 4, "tensors": 6},   # q k v do -> dk dv
}


def flash_call_flops(kernel: str, b: int, h: int, t: int, d: int) -> float:
    return FLASH_KERNELS[kernel]["matmuls"] * 2.0 * b * h * t * t * d * 0.5


def flash_call_bytes(kernel: str, b: int, h: int, t: int, d: int,
                     itemsize: int = 2) -> float:
    return FLASH_KERNELS[kernel]["tensors"] * float(b * h * t * d) * itemsize


def least_seconds(flops: float, nbytes: float, device_kind: str
                  ) -> tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    p = peaks(device_kind)
    tc = flops / p["bf16_flops_per_s"]
    tm = nbytes / p["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
