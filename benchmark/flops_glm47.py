"""Operations and bytes of the ``glm4_moe_lite`` block (GLM-4.7-Flash), from
the configuration's sizes alone: what the decode step with experts is asked
to do, for the ``moe_*``, ``expert_gemm_roofline`` and
``latent_attn_roofline`` readers.

A matmul counts once whatever its precision, as ``flops.py`` counts it.
Attention is counted in the absorbed form the decode step is asked for: a
head's query is carried into the latent space, a stored token costs each
head one product with its latent row and rotary key and one sum of the
latent row. Rows that hold nothing (an empty slot, a window's padding, a
token past a request's end) are not work."""

from __future__ import annotations

from typing import Any, Dict


def sizes(config: Dict[str, Any]) -> Dict[str, int]:
    """The configuration's sizes under short names."""
    return {
        "d": int(config["hidden_size"]),
        "h": int(config["num_attention_heads"]),
        "nope": int(config["qk_nope_head_dim"]),
        "rope": int(config["qk_rope_head_dim"]),
        "v": int(config["v_head_dim"]),
        "q": int(config["q_lora_rank"]),
        "c": int(config["kv_lora_rank"]),
        "ff": int(config["intermediate_size"]),
        "f": int(config["moe_intermediate_size"]),
        "e": int(config["n_routed_experts"]),
        "k": int(config["num_experts_per_tok"]),
        "shared": int(config["n_shared_experts"]),
        "dense": int(config["first_k_dense_replace"]),
        "layers": int(config["num_hidden_layers"]),
        "vocab": int(config["vocab_size"]),
    }


def attention_params(s: Dict[str, int]) -> int:
    """A layer's attention matrices and its four norms."""
    return (s["d"] * s["q"] + s["q"] * s["h"] * (s["nope"] + s["rope"])
            + s["d"] * (s["c"] + s["rope"])
            + s["c"] * s["h"] * (s["nope"] + s["v"])
            + s["h"] * s["v"] * s["d"] + s["q"] + s["c"] + 2 * s["d"])


def expert_bytes(config: Dict[str, Any]) -> int:
    """One routed expert's three matrices, float32."""
    s = sizes(config)
    return 3 * s["d"] * s["f"] * 4


def fixed_weight_bytes_per_step(config: Dict[str, Any]) -> int:
    """Bytes of weights every decode step reads whatever its rows pick:
    each layer's attention and norms, the dense layers' MLP, the expert
    layers' router, bias and shared expert, the final norm and the head
    (the embedding rows gathered for a step's tokens are left out)."""
    s = sizes(config)
    n_moe = s["layers"] - s["dense"]
    params = (s["layers"] * attention_params(s)
              + s["dense"] * 3 * s["d"] * s["ff"]
              + n_moe * (s["d"] * s["e"] + s["e"]
                         + s["shared"] * 3 * s["d"] * s["f"])
              + s["d"] + s["vocab"] * s["d"])
    return params * 4


def latent_bytes_per_row(config: Dict[str, Any]) -> int:
    """One stored token of one layer as the model defines it: the latent
    row and the rotary key, float32 (the store pads the key to 128 lanes;
    the padding is not work)."""
    s = sizes(config)
    return (s["c"] + s["rope"]) * 4


def row_flops(config: Dict[str, Any], head: bool) -> float:
    """FLOPs one row (a decode token, or a prompt token in the lane) costs
    a step outside its attention over stored tokens; ``head`` adds the
    unembedding (a lane window unembeds one row only)."""
    s = sizes(config)
    attn = (s["d"] * s["q"] + s["q"] * s["h"] * (s["nope"] + s["rope"])
            + s["d"] * (s["c"] + s["rope"])
            + s["h"] * s["nope"] * s["c"]        # q_nope into the latent
            + s["h"] * s["c"] * s["v"]           # the latent sum out of it
            + s["h"] * s["v"] * s["d"])
    n_moe = s["layers"] - s["dense"]
    mlp = s["dense"] * 3 * s["d"] * s["ff"] + n_moe * (
        s["d"] * s["e"] + (s["k"] + s["shared"]) * 3 * s["d"] * s["f"])
    total = 2.0 * (s["layers"] * attn + mlp)
    if head:
        total += 2.0 * s["d"] * s["vocab"]
    return total


def attended_row_flops(config: Dict[str, Any]) -> float:
    """FLOPs one stored token costs one attending row, all layers: each
    head's score against the latent row and the rotary key, and its sum of
    the latent row."""
    s = sizes(config)
    return 2.0 * s["layers"] * s["h"] * (s["c"] + s["rope"] + s["c"])
