"""Builder ``causal_lm``: a configuration file to a served engine.

Weights are made on the device in one jitted call from the seed, in the
type they are served in; for ``quantize`` the float tree exists only
inside that call, so it is gone before the engine allocates its KV stores.
"""

from __future__ import annotations

from typing import Any, Dict

import jax

from nnstreamer_tpu.models import causal_lm
from nnstreamer_tpu.serving.lm_engine import LMEngine

from ..adapters.lm_engine import LMEngineAdapter
from ..flops import model_dims


def build(config: Dict[str, Any], seed: int) -> LMEngineAdapter:
    m = model_dims(config)
    eng = config["engine"]
    if config.get("precision") != "float32":
        raise ValueError("causal_lm builder serves float32 trees only "
                         "(LMEngine's KV stores are float32)")

    def make(key):
        params = causal_lm.init_causal_lm(
            key, m["vocab"], m["d_model"], m["n_heads"], m["n_layers"],
            m["max_len"], m["d_ff"])
        if config.get("quantize"):
            params = causal_lm.quantize_lm_params(params)
        return params

    params = jax.jit(make)(jax.random.PRNGKey(int(seed)))
    jax.block_until_ready(params)
    engine = LMEngine(params, m["n_heads"], int(eng["max_len"]),
                      n_slots=int(eng["n_slots"]), kv_page_size=0)
    return LMEngineAdapter(engine)
