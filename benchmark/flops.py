"""Operations and bytes of the causal-LM family, from shapes alone.

``prefill_flops`` and ``decode_flops`` are copies of
``nnstreamer_tpu.models.causal_lm``'s closed forms (closed, because XLA's
``cost_analysis`` counts a scan body once). They live here so that no
later PR can change the yardstick. A matmul counts once whatever its
precision: float32 at ``highest`` costs the MXU six bf16 passes, and that
shows as a low share of the bf16 peak, not as more work."""

from __future__ import annotations

from typing import Any, Dict


def _dense(d_model: int, d_ff: int) -> int:
    return 2 * d_model * 3 * d_model + 2 * d_model * d_model \
        + 4 * d_model * d_ff


def prefill_flops(batch: int, seq: int, d_model: int, n_layers: int,
                  vocab: int, d_ff: int = 0) -> float:
    """Forward FLOPs of one prefill (last-token unembed only): per token
    per layer 2·D·3D + 2·D² + 4·D·d_ff; causal attention 2·D·T·(T+1) per
    layer per sequence; plus 2·D·V."""
    d_ff = d_ff or 4 * d_model
    attn = 2 * d_model * seq * (seq + 1)
    return float(batch) * (n_layers * (_dense(d_model, d_ff) * seq + attn)
                           + 2 * d_model * vocab)


def decode_flops(batch: int, pos0: int, n_steps: int, d_model: int,
                 n_layers: int, vocab: int, d_ff: int = 0) -> float:
    """FLOPs of ``n_steps`` KV-cache decode steps starting at cache
    position ``pos0`` (step i attends pos0+i+1 keys; each step pays the
    dense stack plus one unembed)."""
    d_ff = d_ff or 4 * d_model
    attn = 4 * d_model * (n_steps * (pos0 + 1)
                          + n_steps * (n_steps - 1) // 2)
    return float(batch) * (n_layers * (_dense(d_model, d_ff) * n_steps + attn)
                           + n_steps * 2 * d_model * vocab)


def model_dims(config: Dict[str, Any]) -> Dict[str, int]:
    """The sizes the closed forms need, under one set of names."""
    return {"d_model": int(config["n_embd"]),
            "n_layers": int(config["n_layer"]),
            "n_heads": int(config["n_head"]),
            "d_ff": int(config["n_inner"]),
            "vocab": int(config["vocab_size"]),
            "max_len": int(config["n_positions"])}


def weight_bytes_per_step(config: Dict[str, Any]) -> float:
    """Bytes of weights one decode step must read: the four GEMM stacks
    (int8 plus per-channel float32 scales when quantized, float32
    otherwise), the norms, and the tied embedding once for the unembed.
    The embedding and position rows gathered for 8 tokens are left out."""
    m = model_dims(config)
    d, f, n, v = m["d_model"], m["d_ff"], m["n_layers"], m["vocab"]
    gemm = n * (d * 3 * d + d * d + 2 * d * f)
    if config.get("quantize"):
        scales = n * (3 * d + d + f + d) * 4
        stacks = gemm * 1 + scales
    else:
        stacks = gemm * 4
    return float(stacks + n * 2 * d * 4 + d * 4 + v * d * 4)


def kv_bytes_per_token(config: Dict[str, Any]) -> int:
    """K and V rows of one token over all layers, float32 stores."""
    m = model_dims(config)
    return 2 * m["n_layers"] * m["d_model"] * 4


def dgr_bytes_per_row(config: Dict[str, Any]) -> float:
    """Bytes one row costs ``pallas.dequant_gelu_requant`` at least: the
    int32 accumulator in, the int8 activations out, one float32 scale in
    and one out. The (F,) weight scales are shared by all rows of a call
    and left out."""
    f = model_dims(config)["d_ff"]
    return float(f * 4 + f * 1 + 4 + 4)
