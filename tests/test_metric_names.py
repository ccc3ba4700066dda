"""Metric-name lint: the code and the docs cannot drift.

Every metric name literal registered anywhere in ``sitewhere_tpu/``
(counter/meter/timer/histogram calls, plus the extra gauges injected by
the ``GET /metrics`` controller) must

1. appear in the metric inventory of ``docs/OBSERVABILITY.md``, and
2. sanitize (via ``_prom_name``) to a prometheus-legal metric name.
"""

import pathlib
import re

from sitewhere_tpu.runtime.metrics import _prom_name

REPO = pathlib.Path(__file__).resolve().parent.parent
PKG = REPO / "sitewhere_tpu"
DOCS = REPO / "docs" / "OBSERVABILITY.md"

# .counter("name") / .meter( "name" — tolerates a line break before the
# literal; f-strings and computed names (containing "{") are skipped,
# their *prefix* conventions are documented prose-side instead.
_REG_CALL = re.compile(
    r"\.(counter|meter|timer|histogram)\(\s*\"([^\"{]+)\"", re.S)
# extra_gauges keys: extra["k"] = / "k": value. The builder lives on the
# instance now (instance.extra_gauges, shared by GET /metrics and the
# cluster telemetry fan-in); controllers.py stays scanned for any
# endpoint-local additions.
_EXTRA_ITEM = re.compile(
    r"\"((?:cluster|pipeline|hbm)\.[a-z_.0-9]+)\"\s*[:\]]")
# labeled extra-gauge families emitted with a literal label block
# (runtime/hbmledger.py: hbm.table_bytes{table="..."}) — collect the
# family name; the label keys are linted separately below
_LABELED_FAMILY = re.compile(r"\"(hbm\.[a-z_.0-9]+)\"")

# every label KEY that may appear on an exported sample, anywhere —
# labeled histogram children (engine/edge/stage/tenant/consumer), the
# HBM ledger's table label, and the cluster fan-in's injected peer label.
# New label keys are a cardinality decision: add them here AND document
# them.
LABEL_KEY_ALLOW = {"engine", "edge", "stage", "tenant", "table", "peer",
                   "le", "topic", "consumer"}
# no whitespace allowed after { or , : label BLOCKS are written tight
# (`{table="..."` / `,peer="..."`), python kwargs are not (`, name="x"`)
_LABEL_KEY = re.compile(r"(?:\{|,)([a-z_]+)=\\?\"")

_PROM_LEGAL = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")

_EXTRA_FILES = ("instance.py", "web/controllers.py")


def _collect_names():
    names = set()
    for path in sorted(PKG.rglob("*.py")):
        text = path.read_text()
        for _, name in _REG_CALL.findall(text):
            names.add(name)
    for rel in _EXTRA_FILES:
        names.update(_EXTRA_ITEM.findall((PKG / rel).read_text()))
    names.update(_LABELED_FAMILY.findall(
        (PKG / "runtime" / "hbmledger.py").read_text()))
    return names


def test_found_a_plausible_inventory():
    names = _collect_names()
    # the lint is only meaningful if the scan actually sees the code
    assert len(names) > 25, sorted(names)
    assert "pipeline.step_stage_seconds" in names
    assert "events" in names
    assert "cluster.gossip.published" in names
    assert "pipeline.event_age_seconds" in names
    assert "metrics.label_overflow" in names
    assert "hbm.table_bytes" in names
    assert "hbm.total_bytes" in names


def test_exported_label_keys_are_allow_listed():
    """Every label key that can reach a Prometheus sample must come from
    the allow-list: labels are a cardinality commitment (metrics.py caps
    children per family and spills to `_overflow`), so a new key is a
    deliberate decision, not a drive-by."""
    offenders = {}
    for rel in ("runtime/hbmledger.py", "parallel/cluster.py",
                "runtime/eventage.py"):
        text = (PKG / rel).read_text()
        bad = sorted(set(_LABEL_KEY.findall(text)) - LABEL_KEY_ALLOW)
        if bad:
            offenders[rel] = bad
    assert not offenders, (
        f"label keys outside the allow-list (add deliberately to "
        f"LABEL_KEY_ALLOW and document them): {offenders}")


def test_every_metric_name_is_documented():
    docs = DOCS.read_text()
    missing = sorted(n for n in _collect_names() if f"`{n}`" not in docs)
    assert not missing, (
        f"metric names registered in code but absent from "
        f"docs/OBSERVABILITY.md inventory: {missing}")


def test_every_metric_name_is_prometheus_legal():
    bad = sorted(n for n in _collect_names()
                 if not _PROM_LEGAL.match(_prom_name(n)))
    assert not bad, f"names that survive _prom_name illegally: {bad}"


def test_documented_stage_labels_match_flight_stages():
    from sitewhere_tpu.runtime.flight import STAGES

    docs = DOCS.read_text()
    missing = [s for s in STAGES if f"`{s}`" not in docs]
    assert not missing, (
        f"flight stages undocumented in OBSERVABILITY.md: {missing}")


def test_documented_cycle_stages_match_flight_cycle_stages():
    from sitewhere_tpu.runtime.flight import CYCLE_STAGES

    docs = DOCS.read_text()
    missing = [s for s in CYCLE_STAGES if f"`{s}`" not in docs]
    assert not missing, (
        f"consumer-cycle stages undocumented in OBSERVABILITY.md: "
        f"{missing}")
