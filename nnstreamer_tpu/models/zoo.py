"""Model zoo registry + the ModelBundle contract used by the xla-tpu backend.

The reference loads opaque model files (.tflite/.pb/.pt) through per-backend
C++ runtimes; the TPU-native equivalent is a *pure function + params* pair
compiled by XLA. ``ModelBundle`` is that contract. Sources:

 * zoo models registered here ("zoo://mobilenet_v2?width=0.25"),
 * user .py files exporting ``make_model(options) -> ModelBundle`` (or dict),
 * in-process callables / flax modules handed directly to ``model=``.

Params checkpointing uses orbax/flax serialization; a bundle may lazily
initialize random params when no checkpoint is given (streaming smoke tests
and benchmarks exercise compute, not trained weights — like the reference's
tests use tiny stand-in models, component-description.md:126).
"""

from __future__ import annotations

import threading
import urllib.parse
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from ..core.types import TensorsInfo

_lock = threading.Lock()
_factories: Dict[str, Callable[..., "ModelBundle"]] = {}


@dataclass
class ModelBundle:
    """A jax-callable model: ``apply(params, *inputs) -> output(s)``.

    ``in_info``/``out_info`` describe per-frame I/O (batch dim included).
    ``preprocess``/``postprocess`` are optional jax-traceable stages the
    pipeline may fuse into the same XLA program as the model.
    """

    name: str
    apply: Callable[..., Any]
    params: Any = None
    in_info: Optional[TensorsInfo] = None
    out_info: Optional[TensorsInfo] = None
    preprocess: Optional[Callable[..., Any]] = None
    postprocess: Optional[Callable[..., Any]] = None
    metadata: Dict[str, Any] = field(default_factory=dict)

    def fn(self) -> Callable[..., Any]:
        """Params-closed pure function over input arrays."""
        params = self.params
        apply = self.apply
        if params is None:
            return apply
        return lambda *xs: apply(params, *xs)


def init_variables(module: Any, seed: int, *dummies: Any) -> Any:
    """Zoo-model initialization: flax's exact ``init`` compiled into ONE
    XLA program (eager init is hundreds of tiny dispatches). The same
    path on every platform, so a seed names the same weights on the chip
    and in the CPU tests. Zoo weights are untrained placeholders;
    checkpoints (``custom="arch=..."``) replace them for real serving."""
    import jax

    key = jax.random.PRNGKey(int(seed))
    return jax.jit(lambda k: module.init(k, *dummies))(key)


_aliases: Dict[str, str] = {}


def register_model(name: str, factory: Callable[..., ModelBundle]) -> None:
    """Register a zoo factory. A direct registration always wins: it drops
    any alias previously installed under the same name (user factories must
    never be silently shadowed by built-in aliases)."""
    with _lock:
        _factories[name.lower()] = factory
        _aliases.pop(name.lower(), None)


def register_alias(alias: str, canonical: str) -> None:
    """Map ``alias`` onto an existing canonical model name so both resolve
    to the same memoized bundle (one compile). The target is validated
    eagerly; a direct factory under ``alias`` keeps precedence."""
    with _lock:
        target = _aliases.get(canonical.lower(), canonical.lower())
        if target not in _factories:
            raise ValueError(
                f"register_alias: unknown canonical model {canonical!r}")
        _aliases[alias.lower()] = target


def model_names() -> List[str]:
    _ensure_builtin_models()
    with _lock:
        return sorted(set(_factories) | set(_aliases))


#: resolved-bundle memo: repeated ``zoo://`` specs (e.g. a latency and a
#: throughput pipeline over the same model) share one bundle — and through
#: the filter's jit cache, ONE compile. Skipped when an option references a
#: filesystem path (checkpoints may change between loads).
_bundle_memo: Dict[Any, ModelBundle] = {}


def get_model(spec: str, **overrides: Any) -> ModelBundle:
    """Resolve "zoo://name?opt=val" or bare "name"."""
    import os

    _ensure_builtin_models()
    s = spec
    if s.startswith("zoo://"):
        s = s[len("zoo://"):]
    if "?" in s:
        s, qs = s.split("?", 1)
        opts = {k: v[0] for k, v in urllib.parse.parse_qs(qs).items()}
    else:
        opts = {}
    opts.update(overrides)
    with _lock:
        s = s.lower()
        if s not in _factories:  # direct registrations beat aliases
            s = _aliases.get(s, s)
        factory = _factories.get(s)
    if factory is None:
        raise ValueError(f"unknown zoo model {spec!r}; known: {model_names()}")
    cacheable = all(isinstance(v, str) and not os.path.exists(v)
                    for v in opts.values())
    key = (s, tuple(sorted(opts.items()))) if cacheable else None
    if key is not None:
        with _lock:
            hit = _bundle_memo.get(key)
        if hit is not None:
            return hit
    bundle = factory(**opts)
    if key is not None:
        with _lock:
            if len(_bundle_memo) > 64:
                _bundle_memo.clear()
            _bundle_memo[key] = bundle
    return bundle


_builtins_loaded = False


def _ensure_builtin_models() -> None:
    # NOTE: flag is set AFTER the imports: a failing builtin module must
    # surface its ImportError on every call, not leave an empty catalog
    global _builtins_loaded
    if _builtins_loaded:
        return
    from . import mobilenet_v2  # noqa: F401
    from . import mobilenet_v1  # noqa: F401
    from . import simple  # noqa: F401
    from . import ssd_mobilenet  # noqa: F401
    from . import deeplab  # noqa: F401
    from . import posenet  # noqa: F401
    from . import lstm  # noqa: F401
    from . import lenet  # noqa: F401
    from . import stream_transformer  # noqa: F401
    from . import moe_transformer  # noqa: F401
    from . import causal_lm  # noqa: F401
    _builtins_loaded = True
