"""Builder ``qwen3_next``: a configuration file to a served engine.

The weights are drawn on the device leaf by leaf from the seed, by the
configuration's ``weights`` recipe (leaf j of layer l under
``fold_in(fold_in(PRNGKey(seed), l), j)``, routed expert e of a leaf under
one more ``fold_in(.., e)``; the reference draws the same numbers by its own
copy of the recipe): one jitted call a leaf, the held experts of a leaf in
one call, so that set-up's peak is the finished tree's size.
"""

from __future__ import annotations

import math
from concurrent.futures import ThreadPoolExecutor
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from nnstreamer_tpu.models.qwen3_next import Qwen3NextBlock
from nnstreamer_tpu.serving.lm_engine import LMEngine

from ..adapters.lm_engine import LMEngineAdapter

#: a layer's leaves in the order the recipe numbers them: the mixer's, then
#: the experts'
LINEAR = ("w_qkvz", "w_ba", "conv", "w_out")
FULL = ("wq", "wk", "wv", "wo")
MOE = ("router", "w_gate", "w_up", "w_down", "s_gate", "s_up", "s_down",
       "shared_gate")
#: the routed experts' leaves, drawn an expert at a time
EXPERT = ("w_gate", "w_up", "w_down")
#: the leaf number of a linear layer's ``A_log`` (after its mixer's and
#: experts' matrices)
A_LOG = len(LINEAR) + len(MOE)


def check(config: Dict[str, Any]) -> None:
    """Refuse what this block does not compute."""
    want = {"hidden_act": "silu", "norm_topk_prob": True,
            "decoder_sparse_step": 1, "mlp_only_layers": [],
            "tie_word_embeddings": False, "use_sliding_window": False}
    for key, value in want.items():
        if config.get(key, value) != value:
            raise ValueError(f"qwen3_next serves {key}={value!r} only, "
                             f"not {config[key]!r}")


def held(config: Dict[str, Any]) -> Tuple[int, int, int]:
    """(first expert held here, experts held, experts the router scores)."""
    dep = config.get("deployment", {})
    first = int(dep.get("experts_held", [0])[0])
    count = int(config["num_experts"])
    return first, count, int(dep.get("router_experts", count))


def block_of(config: Dict[str, Any]) -> Qwen3NextBlock:
    hd = int(config["head_dim"])
    return Qwen3NextBlock(
        n_heads=int(config["num_attention_heads"]),
        n_kv_heads=int(config["num_key_value_heads"]),
        head_dim=hd,
        rotary_dim=int(round(hd * float(config["partial_rotary_factor"]))),
        lin_key_heads=int(config["linear_num_key_heads"]),
        lin_value_heads=int(config["linear_num_value_heads"]),
        lin_key_dim=int(config["linear_key_head_dim"]),
        lin_value_dim=int(config["linear_value_head_dim"]),
        interval=int(config["full_attention_interval"]),
        top_k=int(config["num_experts_per_tok"]),
        held_from=held(config)[0],
        rope_theta=float(config["rope_theta"]),
        eps=float(config["rms_norm_eps"]))


def leaf_shapes(config: Dict[str, Any], layer: int
                ) -> Dict[str, Tuple[int, ...]]:
    """name -> shape of the matrices of ``layer``, each (.., in, out); a
    routed expert's three are one expert's."""
    c = config
    d, hd = int(c["hidden_size"]), int(c["head_dim"])
    hq, hkv = int(c["num_attention_heads"]), int(c["num_key_value_heads"])
    hk, h = int(c["linear_num_key_heads"]), int(c["linear_num_value_heads"])
    dk, dv = int(c["linear_key_head_dim"]), int(c["linear_value_head_dim"])
    f, fs = int(c["moe_intermediate_size"]), \
        int(c["shared_expert_intermediate_size"])
    out = {"router": (d, held(c)[2]), "w_gate": (d, f), "w_up": (d, f),
           "w_down": (f, d), "s_gate": (d, fs), "s_up": (d, fs),
           "s_down": (fs, d), "shared_gate": (d, 1)}
    if (layer + 1) % int(c["full_attention_interval"]) == 0:
        out.update(wq=(d, hq * 2 * hd), wk=(d, hkv * hd), wv=(d, hkv * hd),
                   wo=(hq * hd, d))
    else:
        out.update(w_qkvz=(d, 2 * hk * dk + 2 * h * dv), w_ba=(d, 2 * h),
                   conv=(int(c["linear_conv_kernel_dim"]),
                         2 * hk * dk + h * dv),
                   w_out=(h * dv, d))
    return out


@partial(jax.jit, static_argnames=("shape", "scale"))
def _draw(key, shape, scale):
    return jax.random.normal(key, shape, jnp.float32) * scale


@partial(jax.jit, static_argnames=("shape", "first", "count"))
def _draw_experts(key, shape, first, count):
    keys = jax.vmap(lambda e: jax.random.fold_in(key, e))(
        jnp.arange(first, first + count))
    return jax.vmap(lambda k: jax.random.normal(k, shape, jnp.float32))(
        keys) / math.sqrt(shape[-2])


@partial(jax.jit, static_argnames=("n",))
def _a_log(key, n):
    return jnp.log(jax.random.uniform(key, (n,), jnp.float32, 1.0, 16.0))


def compile_draws(config: Dict[str, Any], first: int, count: int) -> None:
    """Compile the distinct draw programs side by side before drawing: the
    accelerator's compiler runs outside the interpreter's lock (on a TPU
    v5e five programs compiled in 51.5 s one after another and in 16.9 s
    side by side), and the draws then find them compiled."""
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    d, v = int(config["hidden_size"]), int(config["vocab_size"])
    jobs = {("vocab",): lambda: _draw.lower(key, (v, d), 0.02),
            ("a_log",): lambda: _a_log.lower(
                key, int(config["linear_num_value_heads"]))}
    for li in range(int(config["num_hidden_layers"])):
        for name, shape in leaf_shapes(config, li).items():
            if name in EXPERT:
                jobs[("experts", shape)] = partial(
                    _draw_experts.lower, key, shape, first, count)
            else:
                jobs[("draw", shape)] = partial(
                    _draw.lower, key, shape, 1.0 / math.sqrt(shape[-2]))
    with ThreadPoolExecutor(len(jobs)) as pool:
        list(pool.map(lambda job: job().compile(), jobs.values()))


def make_params(config: Dict[str, Any], seed: int,
                experts: Optional[Tuple[int, int]] = None) -> Dict[str, Any]:
    """The program's tree (``models/qwen3_next.py``) from the seed; with
    ``experts`` (first, count) another share of the experts than the one
    the configuration states (tests)."""
    check(config)
    d, v = int(config["hidden_size"]), int(config["vocab_size"])
    first, count = experts or held(config)[:2]
    compile_draws(config, first, count)
    base = jax.random.PRNGKey(int(seed))
    layers: List[Dict[str, Any]] = []
    for li in range(int(config["num_hidden_layers"])):
        shapes = leaf_shapes(config, li)
        full = "wq" in shapes
        key = jax.random.fold_in(base, li)
        layer = {}
        for j, name in enumerate((FULL if full else LINEAR) + MOE):
            k = jax.random.fold_in(key, j)
            if name in EXPERT:
                layer[name] = _draw_experts(k, shapes[name], first, count)
            else:
                layer[name] = _draw(k, shapes[name],
                                    1.0 / math.sqrt(shapes[name][-2]))
        layer["shared_gate"] = layer["shared_gate"][:, 0]
        layer.update(ln1=jnp.zeros((d,)), ln2=jnp.zeros((d,)))
        if full:
            hd = int(config["head_dim"])
            layer.update(q_norm=jnp.zeros((hd,)), k_norm=jnp.zeros((hd,)))
        else:
            h = int(config["linear_num_value_heads"])
            layer.update(
                A_log=_a_log(jax.random.fold_in(key, A_LOG), h),
                dt_bias=jnp.ones((h,)),
                g_norm=jnp.ones((int(config["linear_value_head_dim"]),)))
        layers.append(layer)
    key = jax.random.fold_in(base, len(layers))
    return {"embed": _draw(jax.random.fold_in(key, 0), (v, d), 0.02),
            "head": _draw(jax.random.fold_in(key, 1), (v, d), 0.02),
            "lnf": jnp.zeros((d,)), "layers": layers}


class Qwen3NextAdapter(LMEngineAdapter):
    """``resident`` reads the full layers' K and V rows out of this
    family's stores, ``(S, L_full, Hkv, max_len, hd)`` each, as (L_full *
    Hkv, rows, hd). The recurrent state is not read: the steps of a
    finished request's chunk tail have moved it past its last token, and
    the logits carry it (every served position's logits depend on it)."""

    def resident(self, most: int) -> List[Tuple[Any, np.ndarray, np.ndarray]]:
        eng = self.engine
        if not self._rule_held or eng._kc is None:
            return []
        done = [(slot, req) for slot, req in self._last.items()
                if req.done and eng._slot_req[slot] is None]
        done.sort(key=lambda sr: -(sr[1].prompt.size + len(sr[1].out)))
        kc, vc = eng._kc
        out = []
        for slot, req in done[:most]:
            rows = int(req.prompt.size) + len(req.out) - 1
            flat = lambda z: np.asarray(z[slot, :, :, :rows]).reshape(  # noqa
                -1, rows, z.shape[-1])
            out.append((req, flat(kc), flat(vc)))
        return out


def build(config: Dict[str, Any], seed: int) -> Qwen3NextAdapter:
    if config.get("precision") != "float32" or config.get("quantize"):
        raise ValueError("qwen3_next builder serves float32 trees only")
    eng = config["engine"]
    params = make_params(config, seed)
    jax.block_until_ready(params)
    engine = LMEngine(params, block_of(config), int(eng["max_len"]),
                      n_slots=int(eng["n_slots"]), kv_page_size=0)
    return Qwen3NextAdapter(engine)
