"""nnslint naming family implementation (moved verbatim from
scripts/check_metric_names.py, which remains as a compatibility shim).

Lint registered metric names, span names AND flight-recorder event
types against the repo naming conventions.

Metric convention (docs/observability.md): every metric is
``nnstpu_<layer>_<name>_<unit>`` with

  * layer  in {pipeline, query, serving, resilience, chaos, router,
    profile},
  * counters    ending in ``_total``,
  * histograms  ending in ``_seconds``,
  * gauges      ending in one of ``_depth`` / ``_slots`` / ``_bytes`` /
    ``_state`` / ``_pages`` / ``_ratio`` / ``_flops``,
  * label keys matching ``[a-z_][a-z0-9_]*``, never the reserved
    ``instance``/``role`` (appended by fleet federation) or ``le``
    (histogram encoder), and at most 8 keys per family (cardinality
    guard).

Span convention (docs/observability.md "Tracing"): every span name is
a literal lowercase dotted ``<layer>.<operation>`` with layer in
{pipeline, query, serving, device} — e.g. ``serving.prefill``.

Event convention (docs/observability.md "Health & flight recorder"):
every flight-recorder event type is the same lowercase dotted
``<layer>.<event>`` shape, with layer additionally allowing {core, obs}
(the log bridge and the obs subsystem itself emit events) — e.g.
``pipeline.stall``, ``query.reconnect_storm``, ``core.log``.

KV-cache placement (docs/performance.md "Paged KV cache"): every
``serving`` metric whose body starts with ``kv_`` belongs to the paged
KV cache and is registered in nnstreamer_tpu/serving/ — no other
package invents ``kv_*`` serving series, and the ``pages`` gauge unit
is reserved for those bodies (a ``_pages`` gauge outside the kv family
is a naming drift, not a new convention). check_kv enforces both
directions, mirroring check_resilience.

Resilience placement (docs/resilience.md): the ``resilience``/``chaos``
metric + event layers belong to nnstreamer_tpu/resilience/ — every
CircuitBreaker/RetryPolicy/FaultPlan series is registered there (other
modules record through its helpers), and conversely the resilience
package never registers under another layer's name. check_resilience
enforces both directions so policy telemetry can't drift into ad-hoc
per-module names.

Profile placement (docs/observability.md "Profiling"): the
``profile`` metric + event layer belongs to nnstreamer_tpu/obs/
profile.py — dispatch timing, jit-cache/compile telemetry, and the
MFU/roofline gauges are registered there only (other modules feed them
through the profiler hooks, never by minting profile.* names), and the
dimensionless ``ratio`` and ``flops`` gauge units are reserved to that
layer (an efficiency ratio elsewhere should be a profile gauge, not a
convention fork). check_profile enforces both directions, mirroring
check_resilience.

SLO placement (docs/observability.md "SLO & tenant accounting"): the
``slo`` metric + event layer belongs to nnstreamer_tpu/obs/slo.py —
per-tenant cost attribution, goodput counters, and burn-rate gauges
are registered there only (dispatch sites feed the accountant through
its hooks, never by minting slo.* names), and the ``tenant`` label is
reserved to obs/slo.py and nnstreamer_tpu/sched/ (everywhere else a
tenant-keyed series is an unbounded-cardinality bug — route it through
the SLO registry, which folds overflow tenants). The ``ratio`` gauge
unit reservation is shared with the profile layer
(``nnstpu_slo_burn_ratio``). check_slo enforces all three directions,
mirroring check_profile.

Fleet placement (docs/autoscale.md): ``nnstpu_fleet_*`` metric series,
``fleet.*`` spans, and the ``fleet.scale_*``/``fleet.migrate_*`` event
subfamilies belong to nnstreamer_tpu/fleet/ — the autoscale controller
and session migrator own the scaling/migration audit trail, while
obs/fleet.py keeps the pre-existing federation events (``fleet.push``,
``fleet.expire``, ...), which is why the fleet *event layer* as a whole
is not package-confined, only those two verb subfamilies. The
``replicas`` gauge unit is reserved to the fleet layer, and
``AUTOSCALE_HOOK`` is assigned only inside nnstreamer_tpu/fleet/ (its
None default plus enable()/disable()) — every other module reads it
behind a single None check, which is what keeps the scheduler's
occupancy tap zero-overhead while autoscaling is off. check_fleet
enforces all of it, mirroring check_tune.

Router placement (docs/resilience.md "Fleet routing & failover"): the
``router`` metric/span/event layer belongs to
nnstreamer_tpu/query/router.py — the multi-backend dispatch telemetry
(placement, failover, backend lifecycle) is registered there only.
check_router enforces it, mirroring check_resilience. Cardinality note:
the ``backend`` label on router series carries configured ``host:port``
endpoints — bounded by fleet size, NEVER per-request/session values.

Diag placement (docs/observability.md "Diagnostics & debug bundles"):
the ``diag`` metric/span/event layer belongs to nnstreamer_tpu/obs/
diag/ — the incident-diagnostics engine back-fills its synthetic
``diag.sched_wait``/``diag.sched_run`` spans (via SpanStore.add_span,
which this lint greps next to start_span) and emits its trigger/bundle
audit events there only, and ``DIAG_HOOK`` is assigned only inside
that package (None default plus enable()/disable()) — consumers read
it behind a single None check, keeping the sched/serving taps
zero-overhead while diagnostics are off. The Prometheus-conventional
``nnstpu_build_info`` identity gauge is exempt from the
<layer>_<name>_<unit> shape and pinned to obs/exporter.py. check_diag
enforces all of it, mirroring check_fleet.

Quality placement (docs/observability.md "Data-plane quality"): the
``quality`` metric/span/event layer belongs to nnstreamer_tpu/obs/
quality/ — per-tap tensor stats, drift gauges, and the anomaly audit
events are registered there only (the element/filter/decoder/serving
taps feed the engine through the None-gated ``QUALITY_HOOK``, never by
minting quality.* names), the ``psi`` gauge unit (population-stability
drift scores) is reserved to that layer, and ``QUALITY_HOOK`` is
assigned only inside that package (None default plus
enable()/disable()) — consumers read it behind a single None check,
which is what keeps the data-plane taps zero-overhead while quality
telemetry is off. check_quality enforces all of it, mirroring
check_diag.

The check greps source for literal first arguments of
``.counter(...)`` / ``.gauge(...)`` / ``.histogram(...)`` registry
calls, ``.start_span(...)`` / ``start_span(...)`` tracing calls, and
``events.record(...)`` / ``_events.record(...)`` / bare ``record(...)``
flight-recorder calls, so drift fails CI (wired as a tier-1 test:
tests/test_metric_names.py) the moment an off-convention name lands.
Registrations built from non-literal names are invisible to this lint —
keep names literal.

Exit 0 when clean; exit 1 listing every violation.
"""

from __future__ import annotations

import ast
import re
import sys
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[2]
SOURCE_ROOT = REPO_ROOT / "nnstreamer_tpu"

LAYERS = ("pipeline", "query", "serving", "resilience", "chaos",
          "router", "profile", "sched", "slo", "disagg", "tune",
          "fleet", "diag", "quality")

#: families exempt from the nnstpu_<layer>_<name>_<unit> shape: the
#: Prometheus-conventional ``<prefix>_build_info`` identity gauge has
#: no unit by design (value is constantly 1; the labels carry the
#: payload) — check_diag pins its one registration to obs/exporter.py
EXEMPT_NAMES = frozenset({"nnstpu_build_info"})
UNIT_BY_TYPE = {
    "counter": ("total",),
    "histogram": ("seconds",),
    # _state: enumerated-condition gauges (e.g. breaker 0/1/2);
    # _pages: KV-page pool occupancy (serving kv_ family only);
    # _ratio/_flops: utilization + roofline gauges (profile layer only);
    # _replicas: live-backend census (fleet controller only);
    # _psi: population-stability drift scores (quality layer only)
    "gauge": ("depth", "slots", "bytes", "state", "pages", "ratio",
              "flops", "replicas", "psi"),
}
#: span layers add "device" — device.xprof has no metric series —
#: and "router" (the dispatch span, query/router.py) and "disagg"
#: (the KV-page transfer span, serving/disagg.py) and "fleet" (the
#: live-migration span, fleet/migrate.py)
#: and "diag" (the synthetic queue-wait/batch-run spans the diag
#: engine back-fills into request traces via SpanStore.add_span,
#: obs/diag/)
SPAN_LAYERS = ("pipeline", "query", "serving", "device", "router",
               "disagg", "fleet", "diag", "quality")
#: event layers additionally allow "core" (the core/log.py bridge),
#: "obs" (the obs subsystem's own events), "fleet" (cross-process
#: federation: push/expiry/merge-conflict audit trail, obs/fleet.py),
#: "resilience"/"chaos" (fault-policy decisions + injected faults,
#: nnstreamer_tpu/resilience/), "router" (multi-backend placement:
#: failover/drain/spill audit trail, query/router.py), and "profile"
#: (capture start/stop audit trail, obs/profile.py), and "sched" (the
#: multi-tenant device scheduler: tenant lifecycle, bucket misses,
#: starvation reliefs — nnstreamer_tpu/sched/), and "slo" (per-tenant
#: SLO burn alerts/recoveries — obs/slo.py), and "disagg" (the
#: prefill/decode split: re-prefill fallbacks + page spills,
#: serving/disagg.py), and "tune" (the autotuner's sweep/adoption
#: audit trail, nnstreamer_tpu/tune/)
#: and "diag" (the incident-diagnostics subsystem: trigger fires and
#: bundle captures — obs/diag/)
EVENT_LAYERS = ("pipeline", "query", "serving", "device", "core", "obs",
                "fleet", "resilience", "chaos", "router", "profile",
                "sched", "slo", "disagg", "tune", "diag", "quality")

#: layers OWNED by the resilience package: registrations under these
#: names must live in RESILIENCE_DIR and vice versa (see module doc)
RESILIENCE_LAYERS = frozenset({"resilience", "chaos"})
RESILIENCE_DIR = "resilience"

#: the paged KV cache owns the ``kv_``-prefixed serving bodies and the
#: ``pages`` gauge unit: both must stay inside KV_DIR (see module doc)
KV_BODY_PREFIX = "kv_"
KV_DIR = "serving"

#: the ``router`` metric/span/event layer is owned by the query
#: router module alone (see module doc); the path is matched on its
#: final two parts so the lint follows the file, not an absolute root
ROUTER_LAYER = "router"
ROUTER_FILE = ("query", "router.py")

#: the ``profile`` metric/event layer is owned by the profiler module
#: alone, and the ``ratio``/``flops`` gauge units are reserved to it
#: (see module doc); matched like ROUTER_FILE
PROFILE_LAYER = "profile"
PROFILE_FILE = ("obs", "profile.py")
PROFILE_UNITS = frozenset({"ratio", "flops"})

#: the ``slo`` metric/event layer is owned by the per-tenant SLO
#: accountant alone (see module doc); matched like PROFILE_FILE. The
#: ``tenant`` label is bounded there (overflow folding) and in the
#: scheduler's registered-tenant series — anywhere else it is an
#: unbounded-cardinality drift
SLO_LAYER = "slo"
SLO_FILE = ("obs", "slo.py")
TENANT_LABEL = "tenant"

#: the ``sched`` metric/event layer is owned by the multi-tenant device
#: scheduler package (sched/telemetry.py centralizes every
#: registration; engine code and the xla bucket counters go through its
#: helpers — see module doc); matched on the package dir like
#: RESILIENCE_DIR
SCHED_LAYER = "sched"
SCHED_DIR = "sched"

#: the ``disagg`` metric/span/event layer is owned by the
#: disaggregated-serving module alone (see module doc); matched like
#: ROUTER_FILE
DISAGG_LAYER = "disagg"
DISAGG_FILE = ("serving", "disagg.py")

#: label names must be legal Prometheus label identifiers
LABEL_NAME_RE = re.compile(r"^[a-z_][a-z0-9_]*$")
#: labels the fleet layer/format owns: ``instance``/``role`` are
#: appended by the aggregator to every federated series, ``le`` by the
#: histogram encoder — a user metric declaring them would collide
RESERVED_LABELS = frozenset({"instance", "role", "le"})
#: cardinality guard: a family declaring more label keys than this is
#: a combinatorial-explosion bug, not a schema
MAX_LABEL_KEYS = 8

#: reg.counter("name"... — dotted call so plain functions named e.g.
#: ``gauge()`` elsewhere don't false-positive
_CALL_RE = re.compile(
    r"\.(counter|gauge|histogram)\(\s*[\"']([^\"']+)[\"']")

_NAME_RE = re.compile(
    r"^nnstpu_(?P<layer>[a-z0-9]+)_(?P<body>[a-z0-9_]+)_(?P<unit>[a-z0-9]+)$")

#: start_span("name"... — both module-level and store-method calls —
#: plus add_span("name"... (the diag engine's synthetic-span insertion
#: path takes the same literal first argument) and phase(stats, "name"...
#: (obs/tracing.phase: the name is the second argument); \b keeps e.g.
#: ``restart_spanner(`` from matching
_SPAN_CALL_RE = re.compile(
    r"\b(?:(?:start_span|add_span)\(\s*|phase\(\s*[\w.]+\s*,\s*)"
    r"[\"']([^\"']+)[\"']")

_SPAN_NAME_RE = re.compile(
    r"^(?P<layer>[a-z]+)\.(?P<op>[a-z][a-z0-9_]*)$")

#: events.record("type"... / _events.record("type"... / a bare
#: record("type"... (module-internal call in obs/events.py). The
#: lookbehind keeps method calls on OTHER objects — ``stats.record(``,
#: ``._record(`` — from matching; those take no literal name anyway.
_EVENT_CALL_RE = re.compile(
    r"(?:(?<![\w.])record|\b(?:events|_events)\.record)"
    r"\(\s*[\"']([^\"']+)[\"']")

_EVENT_NAME_RE = re.compile(
    r"^(?P<layer>[a-z]+)\.(?P<event>[a-z][a-z0-9_]*)$")


def iter_registrations(root: Path = SOURCE_ROOT):
    """Yield (path, lineno, metric_type, name) for every literal-name
    registry call under ``root``."""
    for path in sorted(root.rglob("*.py")):
        text = path.read_text(encoding="utf-8")
        # whole-file scan: registrations routinely wrap the name onto
        # the line after the open paren (\s* spans newlines)
        for m in _CALL_RE.finditer(text):
            lineno = text.count("\n", 0, m.start()) + 1
            yield path, lineno, m.group(1), m.group(2)


def iter_label_decls(root: Path = SOURCE_ROOT):
    """Yield (path, lineno, name, labelnames) for every registry call
    whose label tuple/list is written as literals. AST-based (unlike
    the name greps) because label tuples routinely share lines with
    help strings containing parens; only literal elements are visible —
    keep label schemas literal, same rule as names."""
    for path in sorted(root.rglob("*.py")):
        try:
            tree = ast.parse(path.read_text(encoding="utf-8"))
        except SyntaxError:
            continue
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in UNIT_BY_TYPE):
                continue
            name = None
            if node.args and isinstance(node.args[0], ast.Constant) \
                    and isinstance(node.args[0].value, str):
                name = node.args[0].value
            if name is None:
                continue
            labels_node = node.args[2] if len(node.args) > 2 else None
            if labels_node is None:
                for kw in node.keywords:
                    if kw.arg == "labelnames":
                        labels_node = kw.value
            if not isinstance(labels_node, (ast.Tuple, ast.List)):
                continue
            labels = [e.value for e in labels_node.elts
                      if isinstance(e, ast.Constant)
                      and isinstance(e.value, str)]
            yield path, node.lineno, name, labels


def check_labels(root: Path = SOURCE_ROOT):
    """Label-name violations: illegal identifiers, reserved names, and
    families declaring more than MAX_LABEL_KEYS keys."""
    problems = []
    for path, lineno, name, labels in iter_label_decls(root):
        where = _where(path, lineno)
        for lbl in labels:
            if not LABEL_NAME_RE.match(lbl):
                problems.append(
                    f"{where}: {name!r} label {lbl!r} does not match "
                    f"{LABEL_NAME_RE.pattern}")
            elif lbl in RESERVED_LABELS:
                problems.append(
                    f"{where}: {name!r} label {lbl!r} is reserved "
                    f"(fleet federation appends instance/role; the "
                    f"histogram encoder owns le)")
        if len(labels) > MAX_LABEL_KEYS:
            problems.append(
                f"{where}: {name!r} declares {len(labels)} label keys "
                f"(> {MAX_LABEL_KEYS}) — cardinality guard")
    return problems


def iter_span_sites(root: Path = SOURCE_ROOT):
    """Yield (path, lineno, span_name) for every literal-name
    ``start_span`` call under ``root``."""
    for path in sorted(root.rglob("*.py")):
        text = path.read_text(encoding="utf-8")
        for m in _SPAN_CALL_RE.finditer(text):
            lineno = text.count("\n", 0, m.start()) + 1
            yield path, lineno, m.group(1)


def iter_event_sites(root: Path = SOURCE_ROOT):
    """Yield (path, lineno, event_type) for every literal-type
    flight-recorder ``record`` call under ``root``."""
    for path in sorted(root.rglob("*.py")):
        text = path.read_text(encoding="utf-8")
        for m in _EVENT_CALL_RE.finditer(text):
            lineno = text.count("\n", 0, m.start()) + 1
            yield path, lineno, m.group(1)


def _where(path: Path, lineno: int) -> str:
    rel = path.relative_to(REPO_ROOT) if REPO_ROOT in path.parents else path
    return f"{rel}:{lineno}"


def check_names(root: Path = SOURCE_ROOT):
    """Metric-name shape/layer/unit violations only (the original
    inline body of check(); split out so the nnslint registry can run
    it as its own rule)."""
    problems = []
    found = 0
    for path, lineno, mtype, name in iter_registrations(root):
        found += 1
        if name in EXEMPT_NAMES:
            continue  # identity gauges; ownership pinned by check_diag
        where = _where(path, lineno)
        m = _NAME_RE.match(name)
        if m is None:
            problems.append(
                f"{where}: {name!r} does not match "
                "nnstpu_<layer>_<name>_<unit>")
            continue
        if m.group("layer") not in LAYERS:
            problems.append(
                f"{where}: {name!r} layer {m.group('layer')!r} not in "
                f"{LAYERS}")
        units = UNIT_BY_TYPE[mtype]
        if m.group("unit") not in units:
            problems.append(
                f"{where}: {name!r} is a {mtype} but unit "
                f"{m.group('unit')!r} not in {units}")
    if found == 0:
        problems.append(
            f"no metric registrations found under {root} — "
            "lint regex out of sync with the registry API?")
    return problems


def check(root: Path = SOURCE_ROOT):
    """Return a list of violation strings (empty = clean)."""
    problems = check_names(root)
    problems += check_labels(root)
    problems += check_spans(root)
    problems += check_events(root)
    problems += check_resilience(root)
    problems += check_kv(root)
    problems += check_router(root)
    problems += check_profile(root)
    problems += check_sched(root)
    problems += check_slo(root)
    problems += check_quality(root)
    return problems


def _is_profile_file(path: Path) -> bool:
    return tuple(path.parts[-2:]) == PROFILE_FILE


def check_profile(root: Path = SOURCE_ROOT):
    """Placement lint for the profiler telemetry: every ``profile``-
    layer metric and event is emitted from nnstreamer_tpu/obs/
    profile.py (dispatch sites feed the profiler through its hooks,
    never by minting profile.* names), the profiler module registers
    under no other layer, and the dimensionless ``ratio``/``flops``
    gauge units stay reserved to the profile layer. Mirrors
    check_resilience + the check_kv unit reservation."""
    problems = []
    for path, lineno, _mtype, name in iter_registrations(root):
        m = _NAME_RE.match(name)
        if m is None:
            continue  # shape violations already reported by check()
        layer = m.group("layer")
        in_file = _is_profile_file(path)
        if layer == PROFILE_LAYER and not in_file:
            problems.append(
                f"{_where(path, lineno)}: {name!r} uses the "
                f"{PROFILE_LAYER!r} layer outside "
                f"nnstreamer_tpu/obs/profile.py — feed the profiler "
                f"through its hooks instead")
        elif in_file and layer != PROFILE_LAYER:
            problems.append(
                f"{_where(path, lineno)}: {name!r} registered inside "
                f"nnstreamer_tpu/obs/profile.py must use the "
                f"{PROFILE_LAYER!r} layer, not {layer!r}")
        elif m.group("unit") in PROFILE_UNITS \
                and layer not in (PROFILE_LAYER, SLO_LAYER):
            # the slo layer shares the dimensionless ``ratio`` unit
            # (burn rate is budget-normalized); check_slo pins those
            # registrations to obs/slo.py
            problems.append(
                f"{_where(path, lineno)}: {name!r} uses the "
                f"{m.group('unit')!r} gauge unit reserved for the "
                f"{PROFILE_LAYER!r}/{SLO_LAYER!r} layers")
    for path, lineno, name in iter_event_sites(root):
        m = _EVENT_NAME_RE.match(name)
        if m is None:
            continue
        if m.group("layer") == PROFILE_LAYER and not _is_profile_file(path):
            problems.append(
                f"{_where(path, lineno)}: event {name!r} uses the "
                f"{PROFILE_LAYER!r} layer outside "
                f"nnstreamer_tpu/obs/profile.py")
    return problems


def _is_router_file(path: Path) -> bool:
    return tuple(path.parts[-2:]) == ROUTER_FILE


def check_router(root: Path = SOURCE_ROOT):
    """Placement lint for the multi-backend routing telemetry: every
    ``router``-layer metric, span, and event is emitted from
    nnstreamer_tpu/query/router.py (other modules reach routing through
    QueryRouter, never by minting router.* names). The reverse
    direction stays loose on purpose — router.py legitimately emits
    under ``resilience`` via the policy helpers."""
    problems = []
    for path, lineno, _mtype, name in iter_registrations(root):
        m = _NAME_RE.match(name)
        if m is None:
            continue  # shape violations already reported by check()
        if m.group("layer") == ROUTER_LAYER and not _is_router_file(path):
            problems.append(
                f"{_where(path, lineno)}: {name!r} uses the "
                f"{ROUTER_LAYER!r} layer outside "
                f"nnstreamer_tpu/query/router.py — routing telemetry "
                f"lives with the router")
    for path, lineno, name in iter_span_sites(root):
        m = _SPAN_NAME_RE.match(name)
        if m is None:
            continue
        if m.group("layer") == ROUTER_LAYER and not _is_router_file(path):
            problems.append(
                f"{_where(path, lineno)}: span {name!r} uses the "
                f"{ROUTER_LAYER!r} layer outside "
                f"nnstreamer_tpu/query/router.py")
    for path, lineno, name in iter_event_sites(root):
        m = _EVENT_NAME_RE.match(name)
        if m is None:
            continue
        if m.group("layer") == ROUTER_LAYER and not _is_router_file(path):
            problems.append(
                f"{_where(path, lineno)}: event {name!r} uses the "
                f"{ROUTER_LAYER!r} layer outside "
                f"nnstreamer_tpu/query/router.py")
    return problems


def _is_disagg_file(path: Path) -> bool:
    return tuple(path.parts[-2:]) == DISAGG_FILE


def check_disagg(root: Path = SOURCE_ROOT):
    """Placement lint for the disaggregated-serving telemetry: every
    ``disagg``-layer metric, span, and event is emitted from
    nnstreamer_tpu/serving/disagg.py (engines and the router reach the
    split through DisaggClient/DisaggWorker, never by minting disagg.*
    names). The reverse direction stays loose on purpose — disagg.py
    legitimately rides the ``router`` and ``serving`` layers via the
    QueryRouter and kv_cache helpers it builds on. Mirrors
    check_router."""
    problems = []
    for path, lineno, _mtype, name in iter_registrations(root):
        m = _NAME_RE.match(name)
        if m is None:
            continue  # shape violations already reported by check()
        if m.group("layer") == DISAGG_LAYER and not _is_disagg_file(path):
            problems.append(
                f"{_where(path, lineno)}: {name!r} uses the "
                f"{DISAGG_LAYER!r} layer outside "
                f"nnstreamer_tpu/serving/disagg.py — disaggregation "
                f"telemetry lives with the split")
    for path, lineno, name in iter_span_sites(root):
        m = _SPAN_NAME_RE.match(name)
        if m is None:
            continue
        if m.group("layer") == DISAGG_LAYER and not _is_disagg_file(path):
            problems.append(
                f"{_where(path, lineno)}: span {name!r} uses the "
                f"{DISAGG_LAYER!r} layer outside "
                f"nnstreamer_tpu/serving/disagg.py")
    for path, lineno, name in iter_event_sites(root):
        m = _EVENT_NAME_RE.match(name)
        if m is None:
            continue
        if m.group("layer") == DISAGG_LAYER and not _is_disagg_file(path):
            problems.append(
                f"{_where(path, lineno)}: event {name!r} uses the "
                f"{DISAGG_LAYER!r} layer outside "
                f"nnstreamer_tpu/serving/disagg.py")
    return problems


def check_kv(root: Path = SOURCE_ROOT):
    """Placement lint for the paged-KV-cache telemetry: every
    ``serving`` metric with a ``kv_``-prefixed body is registered under
    nnstreamer_tpu/serving/ (the cache records its own pool/prefix
    series — other modules read them through the registry), and the
    ``pages`` gauge unit never appears outside that family."""
    problems = []
    for path, lineno, _mtype, name in iter_registrations(root):
        m = _NAME_RE.match(name)
        if m is None:
            continue  # shape violations already reported by check()
        is_kv = (m.group("layer") == "serving"
                 and m.group("body").startswith(KV_BODY_PREFIX))
        in_pkg = KV_DIR in path.parts
        if is_kv and not in_pkg:
            problems.append(
                f"{_where(path, lineno)}: {name!r} uses the serving "
                f"{KV_BODY_PREFIX}* body outside "
                f"nnstreamer_tpu/{KV_DIR}/ — the paged KV cache owns "
                f"that family")
        elif m.group("unit") == "pages" and not is_kv:
            problems.append(
                f"{_where(path, lineno)}: {name!r} uses the 'pages' "
                f"gauge unit reserved for serving "
                f"{KV_BODY_PREFIX}* bodies")
    return problems


def check_resilience(root: Path = SOURCE_ROOT):
    """Placement lint for the fault-policy telemetry: every metric in
    the ``resilience``/``chaos`` layers is registered under
    nnstreamer_tpu/resilience/ (breaker/retry/shed/fallback series are
    the policy objects' own — other modules go through their helpers),
    and the resilience package registers under no other layer."""
    problems = []
    for path, lineno, _mtype, name in iter_registrations(root):
        m = _NAME_RE.match(name)
        if m is None:
            continue  # shape violations already reported by check()
        layer = m.group("layer")
        in_pkg = RESILIENCE_DIR in path.parts
        if layer in RESILIENCE_LAYERS and not in_pkg:
            problems.append(
                f"{_where(path, lineno)}: {name!r} uses the {layer!r} "
                f"layer outside nnstreamer_tpu/{RESILIENCE_DIR}/ — "
                f"record through resilience.policy/chaos helpers instead")
        elif in_pkg and layer not in RESILIENCE_LAYERS:
            problems.append(
                f"{_where(path, lineno)}: {name!r} registered inside "
                f"nnstreamer_tpu/{RESILIENCE_DIR}/ must use a layer in "
                f"{sorted(RESILIENCE_LAYERS)}, not {layer!r}")
    return problems


def check_sched(root: Path = SOURCE_ROOT):
    """Placement lint for the device-scheduler telemetry: every metric
    and event in the ``sched`` layer is emitted from
    nnstreamer_tpu/sched/ (sched/telemetry.py centralizes the
    registrations; the xla bucket counters and engine events go through
    its helper functions, never by minting sched.* names elsewhere),
    and the sched package registers under no other layer. Mirrors
    check_resilience."""
    problems = []
    for path, lineno, _mtype, name in iter_registrations(root):
        m = _NAME_RE.match(name)
        if m is None:
            continue  # shape violations already reported by check()
        layer = m.group("layer")
        in_pkg = SCHED_DIR in path.parts
        if layer == SCHED_LAYER and not in_pkg:
            problems.append(
                f"{_where(path, lineno)}: {name!r} uses the "
                f"{SCHED_LAYER!r} layer outside nnstreamer_tpu/"
                f"{SCHED_DIR}/ — record through sched.telemetry "
                f"helpers instead")
        elif in_pkg and layer != SCHED_LAYER:
            problems.append(
                f"{_where(path, lineno)}: {name!r} registered inside "
                f"nnstreamer_tpu/{SCHED_DIR}/ must use the "
                f"{SCHED_LAYER!r} layer, not {layer!r}")
    for path, lineno, name in iter_event_sites(root):
        m = _EVENT_NAME_RE.match(name)
        if m is None:
            continue
        if m.group("layer") == SCHED_LAYER and SCHED_DIR not in path.parts:
            problems.append(
                f"{_where(path, lineno)}: event {name!r} uses the "
                f"{SCHED_LAYER!r} layer outside nnstreamer_tpu/"
                f"{SCHED_DIR}/")
    return problems


def _is_slo_file(path: Path) -> bool:
    return tuple(path.parts[-2:]) == SLO_FILE


def check_slo(root: Path = SOURCE_ROOT):
    """Placement lint for the per-tenant SLO accountant: every
    ``slo``-layer metric and event is emitted from
    nnstreamer_tpu/obs/slo.py (the scheduler, serving engines, and
    router feed it through the None-gated hooks, never by minting
    slo.* names), the accountant registers under no other layer, and
    the ``tenant`` label stays inside obs/slo.py + nnstreamer_tpu/
    sched/ — the two places that bound it (overflow folding / the
    registered-tenant set). Mirrors check_profile + the check_kv
    reservation, but for a label key instead of a unit."""
    problems = []
    for path, lineno, _mtype, name in iter_registrations(root):
        m = _NAME_RE.match(name)
        if m is None:
            continue  # shape violations already reported by check()
        layer = m.group("layer")
        in_file = _is_slo_file(path)
        if layer == SLO_LAYER and not in_file:
            problems.append(
                f"{_where(path, lineno)}: {name!r} uses the "
                f"{SLO_LAYER!r} layer outside nnstreamer_tpu/obs/"
                f"slo.py — feed the SLO accountant through its hooks "
                f"instead")
        elif in_file and layer != SLO_LAYER:
            problems.append(
                f"{_where(path, lineno)}: {name!r} registered inside "
                f"nnstreamer_tpu/obs/slo.py must use the "
                f"{SLO_LAYER!r} layer, not {layer!r}")
    for path, lineno, name, labels in iter_label_decls(root):
        if TENANT_LABEL in labels and not _is_slo_file(path) \
                and SCHED_DIR not in path.parts:
            problems.append(
                f"{_where(path, lineno)}: {name!r} declares the "
                f"{TENANT_LABEL!r} label outside nnstreamer_tpu/obs/"
                f"slo.py and nnstreamer_tpu/{SCHED_DIR}/ — per-tenant "
                f"series are bounded only there (cardinality guard)")
    for path, lineno, name in iter_event_sites(root):
        m = _EVENT_NAME_RE.match(name)
        if m is None:
            continue
        if m.group("layer") == SLO_LAYER and not _is_slo_file(path):
            problems.append(
                f"{_where(path, lineno)}: event {name!r} uses the "
                f"{SLO_LAYER!r} layer outside nnstreamer_tpu/obs/"
                f"slo.py")
    return problems


def check_spans(root: Path = SOURCE_ROOT):
    """Span-name violations under ``root``. Zero span sites is only a
    problem for the real source tree (the metric check already guards
    arbitrary roots; the tracing API might legitimately be absent from
    a tree under test)."""
    problems = []
    found = 0
    for path, lineno, name in iter_span_sites(root):
        found += 1
        where = _where(path, lineno)
        m = _SPAN_NAME_RE.match(name)
        if m is None:
            problems.append(
                f"{where}: span {name!r} does not match lowercase "
                "<layer>.<operation>")
            continue
        if m.group("layer") not in SPAN_LAYERS:
            problems.append(
                f"{where}: span {name!r} layer {m.group('layer')!r} "
                f"not in {SPAN_LAYERS}")
    if found == 0 and root == SOURCE_ROOT:
        problems.append(
            f"no start_span call sites found under {root} — "
            "lint regex out of sync with the tracing API?")
    return problems


def check_events(root: Path = SOURCE_ROOT):
    """Event-type violations under ``root``. Mirrors check_spans: zero
    event sites only flags the real source tree."""
    problems = []
    found = 0
    for path, lineno, name in iter_event_sites(root):
        found += 1
        where = _where(path, lineno)
        m = _EVENT_NAME_RE.match(name)
        if m is None:
            problems.append(
                f"{where}: event {name!r} does not match lowercase "
                "<layer>.<event>")
            continue
        if m.group("layer") not in EVENT_LAYERS:
            problems.append(
                f"{where}: event {name!r} layer {m.group('layer')!r} "
                f"not in {EVENT_LAYERS}")
    if found == 0 and root == SOURCE_ROOT:
        problems.append(
            f"no event record call sites found under {root} — "
            "lint regex out of sync with the events API?")
    return problems


#: literal-label KERNEL_HOOK call (ops/pallas entry points announce the
#: kernels baked into a compiled program); labels are the device-lane
#: vocabulary, so their shape and owner are pinned like metric names
_KERNEL_LABEL_RE = re.compile(r"KERNEL_HOOK\(\s*[\"']([^\"']+)[\"']")
_KERNEL_NAME_RE = re.compile(r"^pallas\.[a-z][a-z0-9_]*$")
PALLAS_DIR = ("ops", "pallas")

#: module-level assignment to the epilogue-fusion selection hook;
#: matches ``EPILOGUE_SELECT_HOOK = ...`` and ``_epi.EPILOGUE_SELECT_HOOK
#: = ...`` alike
_EPILOGUE_HOOK_ASSIGN_RE = re.compile(
    r"^\s*(?:\w+\s*\.\s*)*EPILOGUE_SELECT_HOOK\s*=[^=]", re.MULTILINE)
#: the hook's definition site and its installer (profile.enable/disable)
EPILOGUE_HOOK_OWNERS = (("ops", "epilogue.py"), ("obs", "profile.py"))


def check_epilogue(root: Path = SOURCE_ROOT):
    """Epilogue-fusion naming/placement lint.

    * Pallas kernel labels (literal ``KERNEL_HOOK("...")`` calls) match
      ``pallas.<snake_case>`` and are emitted only from
      nnstreamer_tpu/ops/pallas/ — the device-lane label vocabulary has
      one owner, like metric registrations (check_profile).
    * ``EPILOGUE_SELECT_HOOK`` is assigned only in ops/epilogue.py (its
      None default) and obs/profile.py (enable()/disable() install and
      clear) — every other module may only *read* it behind a single
      None check, which is what keeps the fusion pass zero-overhead
      while profiling is off.
    """
    problems = []
    for path in sorted(root.rglob("*.py")):
        text = path.read_text(encoding="utf-8")
        for m in _KERNEL_LABEL_RE.finditer(text):
            lineno = text.count("\n", 0, m.start()) + 1
            label = m.group(1)
            where = _where(path, lineno)
            if not _KERNEL_NAME_RE.match(label):
                problems.append(
                    f"{where}: Pallas kernel label {label!r} does not "
                    f"match {_KERNEL_NAME_RE.pattern}")
            elif tuple(path.parts[-3:-1]) != PALLAS_DIR:
                problems.append(
                    f"{where}: Pallas kernel label {label!r} emitted "
                    f"outside nnstreamer_tpu/ops/pallas/ — kernel entry "
                    f"points own their labels")
        for m in _EPILOGUE_HOOK_ASSIGN_RE.finditer(text):
            if tuple(path.parts[-2:]) in EPILOGUE_HOOK_OWNERS:
                continue
            lineno = text.count("\n", 0, m.start()) + 1
            problems.append(
                f"{_where(path, lineno)}: EPILOGUE_SELECT_HOOK assigned "
                f"outside ops/epilogue.py + obs/profile.py — consumers "
                f"read the hook behind one None check; only "
                f"profile.enable()/disable() install and clear it")
    return problems


#: the ``tune`` metric/event layer is owned by the autotuner package:
#: knob sites feed the tuner through the None-gated TUNE_HOOK; only the
#: tuner itself counts picks/trials/adoptions (see module doc)
TUNE_LAYER = "tune"
TUNE_DIR = "tune"
#: module-level assignment to the autotuner hook; matches
#: ``TUNE_HOOK = ...`` and ``_tune.TUNE_HOOK = ...`` alike (but not the
#: distinct fleet-side TUNE_PUSH_HOOK/TUNE_ADOPT_HOOK names)
_TUNE_HOOK_ASSIGN_RE = re.compile(
    r"^\s*(?:\w+\s*\.\s*)*TUNE_HOOK\s*=[^=]", re.MULTILINE)
#: the hook's definition site (tune/__init__.py enable()/disable()) and
#: the profiler, which may install/clear it the way it owns
#: EPILOGUE_SELECT_HOOK
TUNE_HOOK_OWNER_DIR = TUNE_DIR
TUNE_HOOK_OWNER_FILES = (("obs", "profile.py"),)


def _is_tune_pkg(path: Path) -> bool:
    return path.parts[-2] == TUNE_DIR


def check_tune(root: Path = SOURCE_ROOT):
    """Autotuner naming/placement lint.

    * ``tune``-layer metrics (``nnstpu_tune_*``) are registered only
      under nnstreamer_tpu/tune/, and registrations inside that package
      use no other layer — the tuner counts its own picks/sweeps/
      adoptions; knob call sites ship no telemetry of their own.
    * ``tune.*`` events are emitted only from nnstreamer_tpu/tune/.
    * ``TUNE_HOOK`` is assigned only inside nnstreamer_tpu/tune/ (the
      None default plus enable()/disable()) and obs/profile.py — every
      other module may only *read* it behind a single None check, which
      is what keeps every wired knob site zero-overhead while tuning
      is off. Mirrors check_epilogue's EPILOGUE_SELECT_HOOK rule.
    """
    problems = []
    for path, lineno, _mtype, name in iter_registrations(root):
        m = _NAME_RE.match(name)
        if m is None:
            continue  # shape violations already reported by check()
        layer = m.group("layer")
        in_pkg = _is_tune_pkg(path)
        if layer == TUNE_LAYER and not in_pkg:
            problems.append(
                f"{_where(path, lineno)}: {name!r} uses the "
                f"{TUNE_LAYER!r} layer outside nnstreamer_tpu/tune/ — "
                f"knob sites feed the tuner through TUNE_HOOK; only "
                f"the tuner counts its own resolutions")
        elif in_pkg and layer != TUNE_LAYER:
            problems.append(
                f"{_where(path, lineno)}: {name!r} registered inside "
                f"nnstreamer_tpu/tune/ must use the {TUNE_LAYER!r} "
                f"layer, not {layer!r}")
    for path, lineno, name in iter_event_sites(root):
        m = _EVENT_NAME_RE.match(name)
        if m is None:
            continue
        if m.group("layer") == TUNE_LAYER and not _is_tune_pkg(path):
            problems.append(
                f"{_where(path, lineno)}: event {name!r} uses the "
                f"{TUNE_LAYER!r} layer outside nnstreamer_tpu/tune/")
    for path in sorted(root.rglob("*.py")):
        text = path.read_text(encoding="utf-8")
        for m in _TUNE_HOOK_ASSIGN_RE.finditer(text):
            if _is_tune_pkg(path) \
                    or tuple(path.parts[-2:]) in TUNE_HOOK_OWNER_FILES:
                continue
            lineno = text.count("\n", 0, m.start()) + 1
            problems.append(
                f"{_where(path, lineno)}: TUNE_HOOK assigned outside "
                f"nnstreamer_tpu/tune/ + obs/profile.py — consumers "
                f"read the hook behind one None check; only "
                f"tune.enable()/disable() install and clear it")
    return problems


#: the ``fleet`` *metric* layer and ``fleet.*`` spans are owned by the
#: autoscale package; the fleet *event* layer is shared with obs/
#: fleet.py (federation audit trail predates the controller), so only
#: the controller's verb subfamilies are package-confined
FLEET_LAYER = "fleet"
FLEET_DIR = "fleet"
#: event subfamilies the controller/migrator own: fleet.scale_up,
#: fleet.scale_in, fleet.migrate_start/done/abandon — obs/fleet.py
#: keeps fleet.push/expire/merge_conflict/drain_confirmed/...
FLEET_EVENT_PREFIXES = ("scale_", "migrate_")
#: the ``replicas`` gauge unit is the controller's census vocabulary
FLEET_UNITS = frozenset({"replicas"})
#: module-level assignment to the autoscale hook; matches
#: ``AUTOSCALE_HOOK = ...`` and ``_fleet.AUTOSCALE_HOOK = ...`` alike
_FLEET_HOOK_ASSIGN_RE = re.compile(
    r"^\s*(?:\w+\s*\.\s*)*AUTOSCALE_HOOK\s*=[^=]", re.MULTILINE)


def _is_fleet_pkg(path: Path) -> bool:
    return path.parts[-2] == FLEET_DIR


def check_fleet(root: Path = SOURCE_ROOT):
    """Autoscaler naming/placement lint.

    * ``fleet``-layer metrics (``nnstpu_fleet_*``) are registered only
      under nnstreamer_tpu/fleet/, and registrations inside that
      package use no other layer — the controller counts its own
      scale actions and migrations; obs/fleet.py (the federation
      aggregator) registers nothing.
    * the ``replicas`` gauge unit stays reserved to the fleet layer
      (a replica census elsewhere should route through the
      controller, not fork the convention).
    * ``fleet.*`` spans are emitted only from nnstreamer_tpu/fleet/.
    * ``fleet.scale_*`` / ``fleet.migrate_*`` events are emitted only
      from nnstreamer_tpu/fleet/ — the fleet event layer itself stays
      open because obs/fleet.py owns the federation subfamily
      (fleet.push, fleet.expire, fleet.drain_confirmed, ...).
    * ``AUTOSCALE_HOOK`` is assigned only inside nnstreamer_tpu/fleet/
      (the None default plus enable()/disable()) — every other module
      may only *read* it behind a single None check, which keeps the
      scheduler's occupancy tap zero-overhead while autoscaling is
      off. Mirrors check_tune's TUNE_HOOK rule.
    """
    problems = []
    for path, lineno, _mtype, name in iter_registrations(root):
        m = _NAME_RE.match(name)
        if m is None:
            continue  # shape violations already reported by check()
        layer = m.group("layer")
        in_pkg = _is_fleet_pkg(path)
        if layer == FLEET_LAYER and not in_pkg:
            problems.append(
                f"{_where(path, lineno)}: {name!r} uses the "
                f"{FLEET_LAYER!r} layer outside nnstreamer_tpu/fleet/ "
                f"— scaling telemetry lives with the controller")
        elif in_pkg and layer != FLEET_LAYER:
            problems.append(
                f"{_where(path, lineno)}: {name!r} registered inside "
                f"nnstreamer_tpu/fleet/ must use the {FLEET_LAYER!r} "
                f"layer, not {layer!r}")
        elif m.group("unit") in FLEET_UNITS and layer != FLEET_LAYER:
            problems.append(
                f"{_where(path, lineno)}: {name!r} uses the "
                f"{m.group('unit')!r} gauge unit reserved for the "
                f"{FLEET_LAYER!r} layer")
    for path, lineno, name in iter_span_sites(root):
        m = _SPAN_NAME_RE.match(name)
        if m is None:
            continue
        if m.group("layer") == FLEET_LAYER and not _is_fleet_pkg(path):
            problems.append(
                f"{_where(path, lineno)}: span {name!r} uses the "
                f"{FLEET_LAYER!r} layer outside nnstreamer_tpu/fleet/")
    for path, lineno, name in iter_event_sites(root):
        m = _EVENT_NAME_RE.match(name)
        if m is None:
            continue
        if m.group("layer") == FLEET_LAYER \
                and m.group("event").startswith(FLEET_EVENT_PREFIXES) \
                and not _is_fleet_pkg(path):
            problems.append(
                f"{_where(path, lineno)}: event {name!r} uses a fleet "
                f"scale_*/migrate_* subfamily outside nnstreamer_tpu/"
                f"fleet/ — the controller owns the scaling audit trail")
    for path in sorted(root.rglob("*.py")):
        text = path.read_text(encoding="utf-8")
        for m in _FLEET_HOOK_ASSIGN_RE.finditer(text):
            if _is_fleet_pkg(path):
                continue
            lineno = text.count("\n", 0, m.start()) + 1
            problems.append(
                f"{_where(path, lineno)}: AUTOSCALE_HOOK assigned "
                f"outside nnstreamer_tpu/fleet/ — consumers read the "
                f"hook behind one None check; only fleet.enable()/"
                f"disable() install and clear it")
    return problems


#: fleet/checkpoint.py's vocabulary: the crash-checkpoint metric
#: families, the fleet.checkpoint_*/restore_* event subfamilies, and
#: the CHECKPOINT_HOOK push-doc tap
CHECKPOINT_METRIC_PREFIXES = ("nnstpu_fleet_checkpoint_",
                              "nnstpu_fleet_restore_",
                              "nnstpu_fleet_restored_")
CHECKPOINT_EVENT_PREFIXES = ("checkpoint_", "restore_")
#: module-level assignment to the checkpoint watermark hook; matches
#: ``CHECKPOINT_HOOK = ...`` and ``_obsfleet.CHECKPOINT_HOOK = ...``
_CKPT_HOOK_ASSIGN_RE = re.compile(
    r"^[ \t]*(?:\w+[ \t]*\.[ \t]*)*CHECKPOINT_HOOK[ \t]*=[^=]",
    re.MULTILINE)
#: the hook's None default lives on the push-doc schema owner,
#: obs/fleet.py — the one assignment allowed outside fleet/
CKPT_HOOK_HOME = ("obs", "fleet.py")


def check_checkpoint(root: Path = SOURCE_ROOT):
    """Crash-checkpoint naming/placement lint (check_fleet's sibling).

    * the ``nnstpu_fleet_checkpoint_*`` / ``nnstpu_fleet_restore_*`` /
      ``nnstpu_fleet_restored_*`` metric families are registered only
      under nnstreamer_tpu/fleet/ — snapshot and restore accounting
      lives with the daemon and restorer, not scattered across the
      serving wire that merely carries the blobs.
    * ``fleet.checkpoint_*`` / ``fleet.restore_*`` events are emitted
      only from nnstreamer_tpu/fleet/ — the scale_*/migrate_* rule's
      sibling: one audit trail per subsystem owner.
    * ``CHECKPOINT_HOOK`` is assigned only inside nnstreamer_tpu/
      fleet/ (the daemon's install_hook()/uninstall_hook()), plus the
      ``= None`` default on obs/fleet.py where the hook lives —
      everything else reads it behind one None check, so push docs
      stay zero-overhead when no daemon runs.
    """
    problems = []
    for path, lineno, _mtype, name in iter_registrations(root):
        if name.startswith(CHECKPOINT_METRIC_PREFIXES) \
                and not _is_fleet_pkg(path):
            problems.append(
                f"{_where(path, lineno)}: {name!r} uses the fleet "
                f"checkpoint/restore metric family outside "
                f"nnstreamer_tpu/fleet/ — snapshot accounting lives "
                f"with the checkpoint daemon")
    for path, lineno, name in iter_event_sites(root):
        m = _EVENT_NAME_RE.match(name)
        if m is None:
            continue
        if m.group("layer") == FLEET_LAYER \
                and m.group("event").startswith(CHECKPOINT_EVENT_PREFIXES) \
                and not _is_fleet_pkg(path):
            problems.append(
                f"{_where(path, lineno)}: event {name!r} uses the fleet "
                f"checkpoint_*/restore_* subfamily outside "
                f"nnstreamer_tpu/fleet/ — the daemon and restorer own "
                f"the crash audit trail")
    for path in sorted(root.rglob("*.py")):
        text = path.read_text(encoding="utf-8")
        for m in _CKPT_HOOK_ASSIGN_RE.finditer(text):
            if _is_fleet_pkg(path):
                continue
            line = text[m.start():].splitlines()[0]
            if tuple(path.parts[-2:]) == CKPT_HOOK_HOME \
                    and line.split("=", 1)[1].strip() == "None":
                continue  # the hook's None default on its home module
            lineno = text.count("\n", 0, m.start()) + 1
            problems.append(
                f"{_where(path, lineno)}: CHECKPOINT_HOOK assigned "
                f"outside nnstreamer_tpu/fleet/ — consumers read the "
                f"hook behind one None check; only the daemon's "
                f"install_hook()/uninstall_hook() write it")
    return problems


#: the ``diag`` metric/span/event layer is owned by the incident-
#: diagnostics package (obs/diag/): synthetic queue-wait/batch-run
#: spans, trigger/bundle events, and any diag series are emitted
#: there only. The ``nnstpu_build_info`` identity gauge is registered
#: once, in obs/exporter.py (it serves /debug/version too).
DIAG_LAYER = "diag"
DIAG_PKG = ("obs", "diag")
BUILD_INFO_NAME = "nnstpu_build_info"
BUILD_INFO_FILE = ("obs", "exporter.py")
#: module-level assignment to the diag hook; matches ``DIAG_HOOK =
#: ...`` and ``_diag.DIAG_HOOK = ...`` alike. Cannot match the
#: distinct fleet-side ``DIAG_PUSH_HOOK`` name (obs/fleet.py owns
#: that slot; diag.enable()/disable() install and clear it)
_DIAG_HOOK_ASSIGN_RE = re.compile(
    r"^\s*(?:\w+\s*\.\s*)*DIAG_HOOK\s*=[^=]", re.MULTILINE)


def _is_diag_pkg(path: Path) -> bool:
    return tuple(path.parts[-3:-1]) == DIAG_PKG


def check_diag(root: Path = SOURCE_ROOT):
    """Incident-diagnostics naming/placement lint.

    * ``diag``-layer metrics are registered only under
      nnstreamer_tpu/obs/diag/, and the ``nnstpu_build_info`` identity
      gauge (exempt from the <layer>_<name>_<unit> shape) only in
      obs/exporter.py.
    * ``diag.*`` spans — the synthetic sched_wait/sched_run spans the
      engine back-fills via ``SpanStore.add_span`` — are created only
      from nnstreamer_tpu/obs/diag/.
    * ``diag.*`` events are emitted only from nnstreamer_tpu/obs/diag/.
    * ``DIAG_HOOK`` is assigned only inside nnstreamer_tpu/obs/diag/
      (the None default plus enable()/disable()) — every other module
      may only *read* it behind a single None check, which is what
      keeps the scheduler and serving taps zero-overhead while
      diagnostics are off. Mirrors check_fleet's AUTOSCALE_HOOK rule.
    """
    problems = []
    for path, lineno, _mtype, name in iter_registrations(root):
        if name == BUILD_INFO_NAME:
            if tuple(path.parts[-2:]) != BUILD_INFO_FILE:
                problems.append(
                    f"{_where(path, lineno)}: {name!r} registered "
                    f"outside nnstreamer_tpu/obs/exporter.py — the "
                    f"build-identity gauge has one owner")
            continue
        m = _NAME_RE.match(name)
        if m is None:
            continue  # shape violations already reported by check()
        if m.group("layer") == DIAG_LAYER and not _is_diag_pkg(path):
            problems.append(
                f"{_where(path, lineno)}: {name!r} uses the "
                f"{DIAG_LAYER!r} layer outside nnstreamer_tpu/obs/"
                f"diag/ — diagnostics telemetry lives with the engine")
    for path, lineno, name in iter_span_sites(root):
        m = _SPAN_NAME_RE.match(name)
        if m is None:
            continue
        if m.group("layer") == DIAG_LAYER and not _is_diag_pkg(path):
            problems.append(
                f"{_where(path, lineno)}: span {name!r} uses the "
                f"{DIAG_LAYER!r} layer outside nnstreamer_tpu/obs/"
                f"diag/ — only the diag engine back-fills synthetic "
                f"spans")
    for path, lineno, name in iter_event_sites(root):
        m = _EVENT_NAME_RE.match(name)
        if m is None:
            continue
        if m.group("layer") == DIAG_LAYER and not _is_diag_pkg(path):
            problems.append(
                f"{_where(path, lineno)}: event {name!r} uses the "
                f"{DIAG_LAYER!r} layer outside nnstreamer_tpu/obs/"
                f"diag/")
    for path in sorted(root.rglob("*.py")):
        text = path.read_text(encoding="utf-8")
        for m in _DIAG_HOOK_ASSIGN_RE.finditer(text):
            if _is_diag_pkg(path):
                continue
            lineno = text.count("\n", 0, m.start()) + 1
            problems.append(
                f"{_where(path, lineno)}: DIAG_HOOK assigned outside "
                f"nnstreamer_tpu/obs/diag/ — consumers read the hook "
                f"behind one None check; only diag.enable()/disable() "
                f"install and clear it")
    return problems


#: the ``quality`` metric/span/event layer is owned by the data-plane
#: quality package (obs/quality/): per-tap stat/drift series and the
#: anomaly audit events are emitted there only, and the ``psi`` gauge
#: unit (population-stability drift scores) is reserved to it
QUALITY_LAYER = "quality"
QUALITY_PKG = ("obs", "quality")
QUALITY_UNITS = frozenset({"psi"})
#: module-level assignment to the quality hook; matches
#: ``QUALITY_HOOK = ...`` and ``_quality.QUALITY_HOOK = ...`` alike
_QUALITY_HOOK_ASSIGN_RE = re.compile(
    r"^\s*(?:\w+\s*\.\s*)*QUALITY_HOOK\s*=[^=]", re.MULTILINE)


def _is_quality_pkg(path: Path) -> bool:
    return tuple(path.parts[-3:-1]) == QUALITY_PKG


def check_quality(root: Path = SOURCE_ROOT):
    """Data-plane quality naming/placement lint.

    * ``quality``-layer metrics are registered only under
      nnstreamer_tpu/obs/quality/, and the ``psi`` gauge unit stays
      reserved to that layer (a drift score elsewhere should route
      through the quality engine, not fork the convention).
    * ``quality.*`` spans and events are emitted only from
      nnstreamer_tpu/obs/quality/.
    * ``QUALITY_HOOK`` is assigned only inside nnstreamer_tpu/obs/
      quality/ (the None default plus enable()/disable()) — every
      other module may only *read* it behind a single None check,
      which is what keeps the element/filter/decoder/serving taps
      zero-overhead while quality telemetry is off. Mirrors
      check_diag's DIAG_HOOK rule.
    """
    problems = []
    for path, lineno, _mtype, name in iter_registrations(root):
        m = _NAME_RE.match(name)
        if m is None:
            continue  # shape violations already reported by check()
        layer = m.group("layer")
        in_pkg = _is_quality_pkg(path)
        if layer == QUALITY_LAYER and not in_pkg:
            problems.append(
                f"{_where(path, lineno)}: {name!r} uses the "
                f"{QUALITY_LAYER!r} layer outside nnstreamer_tpu/obs/"
                f"quality/ — taps feed the engine through QUALITY_HOOK;"
                f" only it counts its own observations")
        elif m.group("unit") in QUALITY_UNITS and layer != QUALITY_LAYER:
            problems.append(
                f"{_where(path, lineno)}: {name!r} uses the "
                f"{m.group('unit')!r} gauge unit reserved for the "
                f"{QUALITY_LAYER!r} layer")
    for path, lineno, name in iter_span_sites(root):
        m = _SPAN_NAME_RE.match(name)
        if m is None:
            continue
        if m.group("layer") == QUALITY_LAYER and not _is_quality_pkg(path):
            problems.append(
                f"{_where(path, lineno)}: span {name!r} uses the "
                f"{QUALITY_LAYER!r} layer outside nnstreamer_tpu/obs/"
                f"quality/")
    for path, lineno, name in iter_event_sites(root):
        m = _EVENT_NAME_RE.match(name)
        if m is None:
            continue
        if m.group("layer") == QUALITY_LAYER and not _is_quality_pkg(path):
            problems.append(
                f"{_where(path, lineno)}: event {name!r} uses the "
                f"{QUALITY_LAYER!r} layer outside nnstreamer_tpu/obs/"
                f"quality/")
    for path in sorted(root.rglob("*.py")):
        text = path.read_text(encoding="utf-8")
        for m in _QUALITY_HOOK_ASSIGN_RE.finditer(text):
            if _is_quality_pkg(path):
                continue
            lineno = text.count("\n", 0, m.start()) + 1
            problems.append(
                f"{_where(path, lineno)}: QUALITY_HOOK assigned "
                f"outside nnstreamer_tpu/obs/quality/ — consumers read "
                f"the hook behind one None check; only "
                f"quality.enable()/disable() install and clear it")
    return problems


def main() -> int:
    problems = check()
    if problems:
        for p in problems:
            print(p, file=sys.stderr)
        print(f"{len(problems)} naming violation(s)", file=sys.stderr)
        return 1
    n = sum(1 for _ in iter_registrations())
    nl = sum(len(labels) for *_x, labels in iter_label_decls())
    ns = sum(1 for _ in iter_span_sites())
    ne = sum(1 for _ in iter_event_sites())
    print(f"metric names OK ({n} registrations checked); "
          f"labels OK ({nl} label keys checked); "
          f"span names OK ({ns} call sites checked); "
          f"event names OK ({ne} call sites checked)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
