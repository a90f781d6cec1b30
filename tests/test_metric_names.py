"""Tier-1 wiring for scripts/check_metric_names.py: every registered
metric name must follow nnstpu_<layer>_<name>_<unit>, every literal
span name lowercase <layer>.<operation>, and every flight-recorder
event type lowercase <layer>.<event>."""

import subprocess
import sys
from pathlib import Path

import pytest

pytestmark = pytest.mark.lint

REPO_ROOT = Path(__file__).resolve().parents[1]
SCRIPT = REPO_ROOT / "scripts" / "check_metric_names.py"


def test_lint_passes_on_tree():
    proc = subprocess.run(
        [sys.executable, str(SCRIPT)], capture_output=True, text=True,
        cwd=REPO_ROOT, timeout=60)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "metric names OK" in proc.stdout
    assert "labels OK" in proc.stdout
    assert "span names OK" in proc.stdout
    assert "event names OK" in proc.stdout


def test_lint_catches_violations(tmp_path):
    """The checker actually rejects off-convention names (guards against
    a regex rot that silently passes everything)."""
    sys.path.insert(0, str(REPO_ROOT / "scripts"))
    try:
        import check_metric_names as lint
    finally:
        sys.path.pop(0)
    bad = tmp_path / "bad.py"
    bad.write_text(
        'reg.counter("nnstpu_pipeline_stuff_seconds", "h")\n'   # counter unit
        'reg.gauge("nnstpu_webui_queue_depth", "h")\n'          # bad layer
        'reg.histogram("freeform_name", "h")\n')                # no convention
    problems = lint.check(tmp_path)
    assert len(problems) == 3
    assert any("not in ('total',)" in p for p in problems)
    assert any("layer 'webui'" in p for p in problems)

    empty = tmp_path / "none"
    empty.mkdir()
    assert any("no metric registrations" in p for p in lint.check(empty))


def test_lint_catches_label_violations(tmp_path):
    """Label-name lint: illegal identifiers, reserved fleet/encoder
    names, and the >8-key cardinality guard."""
    sys.path.insert(0, str(REPO_ROOT / "scripts"))
    try:
        import check_metric_names as lint
    finally:
        sys.path.pop(0)
    many = ", ".join(f'"k{i}"' for i in range(9))
    bad = tmp_path / "bad_labels.py"
    bad.write_text(
        'reg.counter("nnstpu_query_a_total", "h", ("element",))\n'  # fine
        'reg.counter("nnstpu_query_b_total", "h", ("Element",))\n'  # case
        'reg.counter("nnstpu_query_c_total", "h", ("instance",))\n' # reserved
        'reg.histogram("nnstpu_query_d_seconds", "h", ("le",))\n'   # reserved
        'reg.gauge("nnstpu_query_e_depth", "h",\n'
        f'          labelnames=[{many}])\n')                         # >8 keys
    problems = lint.check_labels(tmp_path)
    assert len(problems) == 4, problems
    assert any("'Element'" in p for p in problems)
    assert any("'instance'" in p and "reserved" in p for p in problems)
    assert any("'le'" in p and "reserved" in p for p in problems)
    assert any("cardinality guard" in p for p in problems)
    # the real tree's label schemas must stay clean
    assert lint.check_labels() == []


def test_fleet_event_layer_allowed(tmp_path):
    sys.path.insert(0, str(REPO_ROOT / "scripts"))
    try:
        import check_metric_names as lint
    finally:
        sys.path.pop(0)
    ok = tmp_path / "fleet_events.py"
    ok.write_text('_events.record("fleet.push", "m")\n'
                  '_events.record("fleet.expire", "m")\n'
                  '_events.record("fleet.merge_conflict", "m")\n')
    assert lint.check_events(tmp_path) == []


def test_lint_catches_span_violations(tmp_path):
    sys.path.insert(0, str(REPO_ROOT / "scripts"))
    try:
        import check_metric_names as lint
    finally:
        sys.path.pop(0)
    bad = tmp_path / "bad_spans.py"
    bad.write_text(
        'store.start_span("serving.prefill")\n'       # fine
        'store.start_span("webui.render")\n'          # bad layer
        'store.start_span("PipelineElement")\n'       # not dotted
        'store.start_span("query.Recv")\n')           # uppercase op
    problems = lint.check_spans(tmp_path)
    assert len(problems) == 3
    assert any("layer 'webui'" in p for p in problems)
    assert any("'PipelineElement'" in p for p in problems)
    # the real tree must contain literal span call sites — a regex that
    # stops matching the tracing API shows up as this problem
    assert lint.check_spans() == []


def test_lint_reads_phase_call_sites(tmp_path):
    """obs/tracing.phase names a span in its second argument; the lint
    holds those names to the same rule, and finds the engine's nine."""
    sys.path.insert(0, str(REPO_ROOT / "scripts"))
    try:
        import check_metric_names as lint
    finally:
        sys.path.pop(0)
    bad = tmp_path / "bad_phases.py"
    bad.write_text(
        'with _tracing.phase(st, "serving.decode_wait", parent=step):\n'
        'with tracing.phase(self.stats, "serving.DecodeWait"):\n'
        'with phase(st,\n          "webui.render", parent=admit) as p:\n'
        'emphase(st, "not a span")\n')
    problems = lint.check_spans(tmp_path)
    assert len(problems) == 2
    assert any("'serving.DecodeWait'" in p for p in problems)
    assert any("layer 'webui'" in p for p in problems)
    engine = {name for path, _, name in lint.iter_span_sites()
              if path.name == "lm_engine.py"}
    assert {"serving.step", "serving.admit", "serving.admit_host",
            "serving.prefill_dispatch", "serving.slot_insert",
            "serving.first_token_wait", "serving.decode_dispatch",
            "serving.decode_wait", "serving.retire"} <= engine


def test_lint_catches_event_violations(tmp_path):
    sys.path.insert(0, str(REPO_ROOT / "scripts"))
    try:
        import check_metric_names as lint
    finally:
        sys.path.pop(0)
    bad = tmp_path / "bad_events.py"
    bad.write_text(
        '_events.record("pipeline.stall", "m")\n'     # fine
        'record("query.reconnect_storm", "m")\n'      # fine (bare call)
        '_events.record("webui.boom", "m")\n'         # bad layer
        'events.record("NotDotted", "m")\n'           # not dotted
        'self.stats.record(t0)\n')                    # not an event call
    problems = lint.check_events(tmp_path)
    assert len(problems) == 2
    assert any("layer 'webui'" in p for p in problems)
    assert any("'NotDotted'" in p for p in problems)
    # the real tree must contain literal event call sites — a regex
    # that stops matching the events API shows up as this problem
    assert lint.check_events() == []
