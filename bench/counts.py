"""Operations and bytes the algorithms need, computed from shapes.

Every roofline share and utilization the benchmark reports divides one of
these counts by a time read from the device trace.  The counts are the
least work a correct implementation must do (minimal bytes, causal pairs
only, no recomputation), so a correct kernel can never read above 100%.

Also the peak table: ``peaks(device_kind)`` reads ``peaks.json`` and raises
for a device the table does not list.
"""
from __future__ import annotations

import json
from pathlib import Path

F32 = 4
NS_STEPS = 5


class UnknownDevice(LookupError):
    pass


def peaks(device_kind: str, table: Path = Path(__file__).with_name(
        "peaks.json")) -> dict:
    """Peak FLOP/s, HBM bytes/s and HBM bytes of one chip of this kind."""
    devices = json.loads(Path(table).read_text())["devices"]
    if device_kind not in devices:
        raise UnknownDevice(f"no peaks for device_kind {device_kind!r}; the "
                            f"table lists {sorted(devices)}")
    return devices[device_kind]


def roofline_s(flops: float, nbytes: float, peak: dict) -> tuple:
    """(least seconds the chip needs, 'compute' or 'memory')."""
    tc = flops / peak["flops_per_s"]
    tm = nbytes / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")


# -- model ------------------------------------------------------------------

def layer_matmul_params(m: dict) -> int:
    """Weights a token multiplies through in one decoder layer."""
    d, f = m["d_model"], m["d_ff"]
    q = m["num_heads"] * m["head_dim"]
    kv = m["num_kv_heads"] * m["head_dim"]
    return d * q + 2 * d * kv + q * d + 2 * d * f


def causal_pairs(seq: int) -> int:
    """(query, key) pairs causal attention scores in one sequence."""
    return seq * (seq + 1) // 2


def forward_flops(m: dict, layers: int, batch: int, seq: int) -> float:
    """Model FLOPs of one forward pass over ``batch`` sequences: matmuls
    of every layer and the (tied) head, and causal attention (scores and
    the weighted sum over the causal pairs only)."""
    tokens = batch * seq
    dense = 2 * tokens * (layers * layer_matmul_params(m)
                          + m["d_model"] * m["vocab_size"])
    attn = layers * batch * 4 * m["num_heads"] * m["head_dim"] \
        * causal_pairs(seq)
    return float(dense + attn)


def train_step_flops(m: dict, layers: int, batch: int, seq: int) -> float:
    """Forward and backward (twice the forward); recomputation and the
    optimizer are not counted."""
    return 3.0 * forward_flops(m, layers, batch, seq)


# -- flash attention (causal, training) --------------------------------------

def flash_attention_train(m: dict, layers: int, batch: int, seq: int,
                          dtype_bytes: int = F32) -> tuple:
    """(FLOPs, bytes) of flash attention's forward and backward kernels in
    one training step.  Forward: scores and weighted sum (2 matmuls over
    the causal pairs).  Backward as FlashAttention-2 needs it: scores
    recomputed once, dP, dV, dQ, dK (5 matmuls).  Bytes: forward reads
    q, k, v and writes o; backward reads q, k, v, o, dO and writes
    dq, dk, dv (the per-row statistics are negligible)."""
    H, hd = m["num_heads"], m["head_dim"]
    unit = 2.0 * H * hd * batch * causal_pairs(seq)
    flops = layers * 7 * unit
    act = batch * seq * H * hd * dtype_bytes
    nbytes = layers * (4 + 8) * act
    return flops, float(nbytes)


# -- Newton–Schulz (Muon) ----------------------------------------------------

def newton_schulz(n_in: int, n_out: int, steps: int = NS_STEPS) -> tuple:
    """(FLOPs, bytes) of the quintic iteration on one (n_in, n_out) matrix:
    with m <= n its sides, each iteration forms X X^T (2 m^2 n), its square
    (2 m^3) and the product with X (2 m^2 n).  Bytes: the matrix read and
    the result written once, in f32."""
    m, n = sorted((n_in, n_out))
    flops = steps * (4.0 * m * m * n + 2.0 * m ** 3)
    return flops, float(2 * m * n * F32)


def muon_matrices(m: dict, layers: int) -> list:
    """The (n_in, n_out) shapes Muon orthogonalizes in one step: every
    layer's projections and the token embedding (the position table and
    the norms take normalized SGD)."""
    d, f = m["d_model"], m["d_ff"]
    q = m["num_heads"] * m["head_dim"]
    kv = m["num_kv_heads"] * m["head_dim"]
    per_layer = [(d, q), (d, kv), (d, kv), (q, d), (d, f), (f, d)]
    return per_layer * layers + [(m["vocab_size"], d)]


def newton_schulz_step(m: dict, layers: int) -> tuple:
    flops = nbytes = 0.0
    for n_in, n_out in muon_matrices(m, layers):
        f, b = newton_schulz(n_in, n_out)
        flops += f
        nbytes += b
    return flops, nbytes

