"""``chunk_cut_share`` on hand-made ``LMEngine.stats`` (its arithmetic,
None where the program has no such counter: the parent of the change that
brought it) and the benchmark's adapter over a toy engine with more
requests than slots, where chunks are cut to end where a request ends while
the queue waits: the adapter's prediction of admissions still holds, so
``resident`` returns the rows of the last request of every slot, and they
are the whole-prompt prefill's."""

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.adapters.lm_engine import LMEngineAdapter
from benchmark.layers import chunk_cut_share
from nnstreamer_tpu.models import causal_lm
from nnstreamer_tpu.serving import LMEngine
from nnstreamer_tpu.serving.lm_engine import LANE_ROWS

OLD = {"chunks": 40}
START = {"chunks": 40, "chunks_cut": 10}
END = {"chunks": 240, "chunks_cut": 90}

V, D, H, L = 97, 32, 4, 2
MAXLEN = 2 * LANE_ROWS
SLOTS, CHUNK = 2, 4


def ctx_of(start, end):
    return SimpleNamespace(
        window=SimpleNamespace(stats_start=start, stats_end=end))


def test_share_is_chunks_cut_over_chunks():
    assert chunk_cut_share.read(ctx_of(START, END)) == pytest.approx(40.0)
    # the parent's engine has no such counter
    assert chunk_cut_share.read(ctx_of(OLD, OLD)) is None
    assert chunk_cut_share.read(ctx_of(OLD, END)) is None
    # a window without a chunk
    assert chunk_cut_share.read(ctx_of(START, START)) is None


def test_the_adapter_rule_holds_with_chunks_cut():
    params = causal_lm.init_causal_lm(
        jax.random.PRNGKey(3), V, D, H, L, MAXLEN)
    adapter = LMEngineAdapter(
        LMEngine(params, H, MAXLEN, n_slots=SLOTS, chunk=CHUNK))
    start = adapter.stats()
    rng = np.random.default_rng(11)
    handles = [adapter.submit(rng.integers(0, V, n).astype(np.int32), m)
               for n, m in ((5, 9), (12, 13), (7, 6), (30, 10), (3, 5),
                            (20, 7))]
    while adapter.step():
        pass
    assert all(h.done and len(h.out) == h.max_new for h in handles)
    assert adapter.engine.stats["chunks_cut"] >= 1
    assert chunk_cut_share.read(ctx_of(start, adapter.stats())) > 0.0
    assert adapter._rule_held
    rows = adapter.resident(SLOTS)
    assert len(rows) == SLOTS
    for req, k, v in rows:
        seq = np.concatenate([req.prompt, np.asarray(req.out[:-1], np.int32)])
        _, kc, vc, _ = causal_lm.lm_prefill(
            params, jnp.asarray(seq[None]), H, MAXLEN)
        np.testing.assert_allclose(k, np.asarray(kc)[:, :seq.size],
                                   atol=2e-5)
        np.testing.assert_allclose(v, np.asarray(vc)[:, :seq.size],
                                   atol=2e-5)
