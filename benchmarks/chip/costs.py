"""Peaks of each chip, and the operations and bytes a request needs, from shapes.

Both belong to the yardstick, so they live with the benchmark and not with
the program: a change to the program cannot move them.

`PEAKS` is keyed by JAX's `device_kind`.  A device that is not in the table
is an error, never a default.

Counts are what the algorithm needs, not what a program happens to do:
causal attention counts the S(S+1)/2 query-key pairs it needs, the head
counts the published vocabulary (not the padded one), and bytes count each
weight read once plus a stage's input and output.  Work a program adds on
top (padding rows to a power of two, copying a layer slice, padded vocab
columns) therefore shows as a lower share of the roofline or of the peak.
"""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
    # 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}

BF16 = 2  # bytes per weight and activation element as served
TOKEN = 4  # int32 token id


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def _widths(m: dict) -> tuple[int, int, int, int, int]:
    return (m["d_model"], m["n_heads"], m["kv_heads"], m["head_dim"],
            m["d_ff"])


def layer_params(m: dict) -> int:
    d, h, kh, hd, ff = _widths(m)
    return d * h * hd + 2 * d * kh * hd + h * hd * d + 3 * d * ff + 2 * d


def layer_flops(m: dict, seq: int) -> float:
    """One decoder layer over one sequence: projections, causal attention
    (scores and weighted values over the S(S+1)/2 pairs), SwiGLU."""
    d, h, kh, hd, ff = _widths(m)
    proj = 2 * seq * (d * h * hd + 2 * d * kh * hd + h * hd * d)
    attn = 2 * 2 * h * hd * seq * (seq + 1) / 2
    mlp = 2 * seq * 3 * d * ff
    return proj + attn + mlp


def head_flops(m: dict, seq: int) -> float:
    return 2 * seq * m["d_model"] * m["vocab"]


def request_flops(m: dict, seq: int) -> float:
    """Forward FLOPs of one request (one sequence of `seq` tokens)."""
    return m["n_layers"] * layer_flops(m, seq) + head_flops(m, seq)


def stage_cost(m: dict, seq: int, rows: int, layers: tuple[int, int],
               embeds: bool, unembeds: bool) -> tuple[float, float]:
    """(flops, bytes) a stage needs for `rows` sequences: its layers'
    weights read once, the embedding rows it gathers, the head it applies,
    its input (tokens or hidden states) and its output (hidden states or
    logits over the published vocabulary)."""
    d = m["d_model"]
    n = layers[1] - layers[0]
    flops = rows * n * layer_flops(m, seq)
    nbytes = n * layer_params(m) * BF16
    if embeds:
        nbytes += rows * seq * (TOKEN + d * BF16)
    else:
        nbytes += rows * seq * d * BF16
    if unembeds:
        flops += rows * head_flops(m, seq)
        nbytes += (d * m["vocab"] + d) * BF16 + rows * seq * m["vocab"] * BF16
    else:
        nbytes += rows * seq * d * BF16
    return flops, nbytes


def least_time(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The roofline's least time and which of the two bounds sets it."""
    t_flops = flops / peak["bf16_flops"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")
