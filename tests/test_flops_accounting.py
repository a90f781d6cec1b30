"""FLOPs accounting: analytic closed forms vs XLA cost_analysis.

Pins the empirical premise behind `benchmark/flops.py`'s
`prefill_flops` / `decode_flops` (the closed forms under `decode_mfu`
and `decode_hbm_roofline`): XLA's compiled ``cost_analysis()`` counts a
``lax.scan`` body ONCE regardless of trip count, so layer-scanned models
undercount by ~L. If a jax upgrade changes that accounting, the
L-invariance test here fails and the analytic forms should be
re-validated against the new meaning.
"""

import numpy as np
import pytest

import jax

from benchmark import flops
from nnstreamer_tpu.models import causal_lm
from nnstreamer_tpu.utils import probes

V, D, H, T, B = 512, 128, 4, 128, 2


def _cost_flops(n_layers):
    params = causal_lm.init_causal_lm(
        jax.random.PRNGKey(0), V, D, H, n_layers, T)
    toks = np.zeros((B, T), np.int32)

    def fn(t):
        return causal_lm._lm_prefill(params, t, H, T, flash=False)[0]

    return probes.model_flops(fn, toks)


@pytest.fixture(scope="module")
def cost_by_layers():
    got = {L: _cost_flops(L) for L in (1, 2, 4)}
    if any(v is None for v in got.values()):
        pytest.skip("backend exposes no cost_analysis flops")
    return got


def test_cost_analysis_counts_scan_body_once(cost_by_layers):
    """The wart the analytic forms exist for: reported flops do not grow
    with the scan trip count (so they understate an L-layer model ~Lx)."""
    c1, c2, c4 = (cost_by_layers[k] for k in (1, 2, 4))
    assert c2 < 1.5 * c1, f"L=2 counted {c2 / c1:.2f}x L=1"
    assert c4 < 1.5 * c1, f"L=4 counted {c4 / c1:.2f}x L=1"


def test_analytic_matches_cost_analysis_at_one_layer(cost_by_layers):
    """With no repeated scan body (L=1) the two accountings must agree;
    the analytic form omits LN/softmax/gathers so it sits slightly
    below the XLA count."""
    analytic = flops.prefill_flops(B, T, D, 1, V)
    measured = cost_by_layers[1]
    assert 0.6 * measured < analytic <= 1.1 * measured, \
        f"analytic {analytic:.3e} vs cost_analysis {measured:.3e}"


def test_analytic_scales_linearly_in_layers_and_batch():
    one = flops.prefill_flops(B, T, D, 1, V)
    unembed = B * 2 * D * V
    assert flops.prefill_flops(B, T, D, 8, V) == \
        pytest.approx(8 * (one - unembed) + unembed)
    assert flops.prefill_flops(4 * B, T, D, 1, V) == \
        pytest.approx(4 * one)


def test_decode_flops_matches_single_step_cost_analysis():
    """One decode step at L=1 (no repeated body anywhere): analytic vs
    XLA, same agreement window as prefill."""
    params = causal_lm.init_causal_lm(jax.random.PRNGKey(0), V, D, H, 1, T)
    kc, vc, pos = causal_lm.empty_cache(1, B, H, T, D // H)
    pos0 = 17
    tok = np.zeros((B, 1), np.int32)

    def fn(t, kc, vc):
        return causal_lm._lm_decode_step(
            params, t, kc, vc, np.full((1,), pos0, np.int32), H)[0]

    measured = probes.model_flops(fn, tok, kc, vc)
    if measured is None:
        pytest.skip("backend exposes no cost_analysis flops")
    analytic = flops.decode_flops(B, pos0, 1, D, 1, V)
    assert 0.5 * measured < analytic <= 1.2 * measured, \
        f"analytic {analytic:.3e} vs cost_analysis {measured:.3e}"


def test_decode_flops_attention_term_sums_positions():
    """n_steps from pos0 must equal the sum of single steps (the
    attention term grows with position)."""
    total = flops.decode_flops(B, 10, 5, D, 3, V)
    stepwise = sum(flops.decode_flops(B, 10 + i, 1, D, 3, V)
                   for i in range(5))
    assert total == pytest.approx(stepwise)
