"""Builder ``glm47_flash``: a configuration file to a served engine.

The weights are drawn on the device leaf by leaf from the seed, by the
configuration's ``weights`` recipe (leaf j of layer l under
``fold_in(fold_in(PRNGKey(seed), l), j)``; the reference draws the same
numbers by its own copy of the recipe): one jitted call a leaf, so that
set-up's peak is the finished tree's size and not an expert stack more.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp

from nnstreamer_tpu.models.glm_moe_lite import GlmBlock, split_kv_b
from nnstreamer_tpu.serving.lm_engine import LMEngine

from ..adapters.lm_engine import LMEngineAdapter

#: a layer's matrices in the order the recipe numbers them, under the
#: names the program's tree gives them (``wkv_b`` is split after the draw)
ATTN = ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo")
DENSE = ("w_gate", "w_up", "w_down")
MOE = ("router", "w_gate", "w_up", "w_down", "s_gate", "s_up", "s_down")


def block_of(config: Dict[str, Any]) -> GlmBlock:
    return GlmBlock(
        n_heads=int(config["num_attention_heads"]),
        qk_nope=int(config["qk_nope_head_dim"]),
        qk_rope=int(config["qk_rope_head_dim"]),
        v_head=int(config["v_head_dim"]),
        top_k=int(config["num_experts_per_tok"]),
        route_scale=float(config["routed_scaling_factor"]),
        rope_theta=float(config["rope_theta"]),
        eps=float(config["rms_norm_eps"]))


@partial(jax.jit, static_argnames=("shape", "scale"))
def _draw(key, shape, scale):
    return jax.random.normal(key, shape, jnp.float32) * scale


def make_params(config: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The program's tree (``models/glm_moe_lite.py``) from the seed."""
    block = block_of(config)
    d, v = int(config["hidden_size"]), int(config["vocab_size"])
    h, f = block.n_heads, int(config["moe_intermediate_size"])
    e, ff = int(config["n_routed_experts"]), int(config["intermediate_size"])
    q, c = int(config["q_lora_rank"]), int(config["kv_lora_rank"])
    fs = f * int(config["n_shared_experts"])
    n_layers = int(config["num_hidden_layers"])
    n_dense = int(config["first_k_dense_replace"])
    shapes = {"wq_a": (d, q), "wq_b": (q, h * (block.qk_nope + block.qk_rope)),
              "wkv_a": (d, c + block.qk_rope),
              "wkv_b": (c, h * (block.qk_nope + block.v_head)),
              "wo": (h * block.v_head, d)}
    dense = {"w_gate": (d, ff), "w_up": (d, ff), "w_down": (ff, d)}
    moe = {"router": (d, e), "w_gate": (e, d, f), "w_up": (e, d, f),
           "w_down": (e, f, d), "s_gate": (d, fs), "s_up": (d, fs),
           "s_down": (fs, d)}
    base = jax.random.PRNGKey(int(seed))
    split = jax.jit(split_kv_b, static_argnums=1)
    layers = []
    for li in range(n_layers):
        names = ATTN + (DENSE if li < n_dense else MOE)
        sizes = {**shapes, **(dense if li < n_dense else moe)}
        key = jax.random.fold_in(base, li)
        layer = {name: _draw(jax.random.fold_in(key, j), sizes[name],
                             1.0 / math.sqrt(sizes[name][-2]))
                 for j, name in enumerate(names)}
        layer["wk_b"], layer["wv_b"] = split(layer.pop("wkv_b"), block)
        layer.update(ln1=jnp.ones((d,)), ln2=jnp.ones((d,)),
                     q_norm=jnp.ones((q,)), kv_norm=jnp.ones((c,)))
        if li >= n_dense:
            layer["route_bias"] = jnp.zeros((e,))
        layers.append(layer)
    key = jax.random.fold_in(base, n_layers)
    return {"embed": _draw(jax.random.fold_in(key, 0), (v, d), 0.02),
            "head": _draw(jax.random.fold_in(key, 1), (v, d), 0.02),
            "lnf": jnp.ones((d,)), "layers": layers}


def build(config: Dict[str, Any], seed: int) -> LMEngineAdapter:
    if config.get("precision") != "float32" or config.get("quantize"):
        raise ValueError("glm47_flash builder serves float32 trees only")
    eng = config["engine"]
    params = make_params(config, seed)
    jax.block_until_ready(params)
    engine = LMEngine(params, block_of(config), int(eng["max_len"]),
                      n_slots=int(eng["n_slots"]), kv_page_size=0)
    return LMEngineAdapter(engine)
