"""The chip's peaks, and the operations and bytes each measured program
needs, computed from shapes alone.

A device kind that is not in ``PEAKS`` is an error, never a default.
"""

from __future__ import annotations

#: device_kind as JAX reports it -> published peaks of one chip
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; raises for a kind not in the table."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"the table has {sorted(PEAKS)}")
    return PEAKS[device_kind]


def least_seconds(flops: float, nbytes: float, device_kind: str):
    """``(seconds, bound)``: the larger of operations over peak and bytes
    over bandwidth, and which of the two it is (``"flops"``/``"bytes"``)."""
    pk = peaks(device_kind)
    t_f = flops / pk["bf16_flops_per_s"]
    t_b = nbytes / pk["hbm_bytes_per_s"]
    return (t_f, "flops") if t_f >= t_b else (t_b, "bytes")


# ---------------------------------------------------------------------------
# the victim kernel (kernels/awrp_select.awrp_select_rows)
# ---------------------------------------------------------------------------

_ROW_TILE_BYTES = 512 * 1024  # one plane's tile, as the kernel sizes it


def awrp_select_rows_shape(rows: int, lanes: int):
    """``(rows, lanes)`` of the kernel's operands as it is launched: lanes
    padded to 128, rows to a whole number of row tiles."""
    lanes_p = lanes + (-lanes) % 128
    cap = max(8, (_ROW_TILE_BYTES // (4 * lanes_p)) // 8 * 8)
    tile = min(cap, -(-rows // 8) * 8)
    return -(-rows // tile) * tile, lanes_p


def awrp_select_rows_bytes(rows: int, lanes: int) -> int:
    """HBM bytes of one launch: three ``(rows, lanes)`` int32 planes (F, R,
    valid) and the ``(rows, 1)`` clock in, the ``(rows, 1)`` victims out.
    The kernel's work is VPU integer arithmetic, which has no published
    peak, so its bound is these bytes at peak HBM bandwidth."""
    r, w = awrp_select_rows_shape(rows, lanes)
    return 4 * (3 * r * w + 2 * r)


# ---------------------------------------------------------------------------
# the decoder (a llama-style block: GQA attention + SwiGLU MLP)
# ---------------------------------------------------------------------------


def decoder_sizes(cfg: dict) -> dict:
    """A decoder configuration's sizes under the names used here, from the
    source config's keys (``cfg["config"]``)."""
    c = cfg["config"]
    return {
        "n_layers": c["num_hidden_layers"], "d_model": c["hidden_size"],
        "n_heads": c["num_attention_heads"],
        "n_kv_heads": c["num_key_value_heads"],
        "head_dim": c["hidden_size"] // c["num_attention_heads"],
        "d_ff": c["intermediate_size"], "vocab": c["vocab_size"],
        "rope_theta": c["rope_theta"], "norm_eps": c["rms_norm_eps"],
    }


def _layer_matmul_params(m: dict) -> int:
    d, hd = m["d_model"], m["head_dim"]
    qkv = d * (m["n_heads"] + 2 * m["n_kv_heads"]) * hd
    return qkv + m["n_heads"] * hd * d + 3 * d * m["d_ff"]


def decode_step_flops(m: dict, batch: int, keys: int) -> int:
    """Model FLOPs of one decode step: every sequence's matmuls, attention
    over ``keys`` cached rows, and the output head."""
    per_layer = (2 * _layer_matmul_params(m)
                 + 4 * m["n_heads"] * m["head_dim"] * keys)
    return batch * (m["n_layers"] * per_layer + 2 * m["d_model"] * m["vocab"])


def decode_step_bytes(m: dict, batch: int, keys: int, wbytes: int = 2,
                      kvbytes: int = 2) -> int:
    """HBM bytes one decode step has to read: every weight once (the tied
    embedding as the output head) and each sequence's ``keys`` cached K
    and V rows in every layer."""
    weights = (m["n_layers"] * _layer_matmul_params(m)
               + m["vocab"] * m["d_model"]) * wbytes
    kv = (m["n_layers"] * batch * keys * 2 * m["n_kv_heads"] * m["head_dim"]
          * kvbytes)
    return weights + kv


def prefill_flops(m: dict, batch: int, seq: int) -> int:
    """Model FLOPs of one prefill: matmuls for every prompt token, causal
    attention (each query over itself and the keys before it), and the
    output head at the last position only, the one a server needs."""
    attn = 4 * m["n_heads"] * m["head_dim"] * seq * (seq + 1) // 2
    per_seq = (m["n_layers"] * (2 * _layer_matmul_params(m) * seq + attn)
               + 2 * m["d_model"] * m["vocab"])
    return batch * per_seq
