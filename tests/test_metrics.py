"""Metrics registry + Prometheus exposition tests (ISSUE 7 tentpole).

The contract under test:

* child semantics — counters are monotone, gauges move freely, histograms
  proxy :class:`LatencyHistogram` (so ``.hist`` and the registry read one
  data structure);
* family/registry discipline — name/label validation, idempotent
  registration, kind- and label-set-mismatch rejection, the label-less
  proxy surface;
* exposition — ``metrics_text()`` is valid text format 0.0.4: label
  values escaped (backslash, quote, newline), histogram buckets
  cumulative with ``le="+Inf"`` == ``_count``, and the whole thing
  round-trips through the strict :func:`parse_metrics_text` (the same
  gate the CI scrape smoke uses);
* snapshots — ``snapshot()`` is flat and keyed like the exposition,
  ``delta_since`` reports exactly what moved;
* the parser rejects malformed exposition (no TYPE, duplicates, bad
  escapes, non-numeric values, non-cumulative buckets).
"""
import pytest

from repro.obs import parse_metrics_text
from repro.obs.metrics import (Counter, Gauge, Histogram,
                               MetricsRegistry, escape_label_value)


# =============================================================================
# Children
# =============================================================================

class TestChildren:
    def test_counter_is_monotone(self):
        c = Counter()
        c.inc()
        c.inc(2.5)
        assert c.value == 3.5
        with pytest.raises(ValueError, match="only go up"):
            c.inc(-1)
        assert c.value == 3.5

    def test_gauge_moves_freely(self):
        g = Gauge()
        g.set(10)
        g.dec(4)
        g.inc()
        assert g.value == 7.0

    def test_histogram_value_is_count_and_shares_hist(self):
        h = Histogram()
        h.observe(1e-3)
        h.observe(2e-3)
        assert h.value == 2.0
        assert h.hist.n == 2                     # same object, same counts
        assert h.summary() == h.hist.summary()
        assert h.quantile(0.5) == h.hist.quantile(0.5)


# =============================================================================
# Families + registry discipline
# =============================================================================

class TestRegistry:
    def test_registration_is_idempotent(self):
        r = MetricsRegistry()
        a = r.counter("smof_x_total", "help", ("k",))
        b = r.counter("smof_x_total", "different help ignored", ("k",))
        assert a is b
        assert "smof_x_total" in r and r.get("smof_x_total") is a

    def test_kind_and_labelset_mismatch_rejected(self):
        r = MetricsRegistry()
        r.counter("smof_x_total", labelnames=("k",))
        with pytest.raises(ValueError, match="already registered"):
            r.gauge("smof_x_total", labelnames=("k",))
        with pytest.raises(ValueError, match="already registered"):
            r.counter("smof_x_total", labelnames=("k", "j"))

    @pytest.mark.parametrize("name", ["1bad", "has space", "dash-ed", ""])
    def test_invalid_metric_names_rejected(self, name):
        with pytest.raises(ValueError, match="invalid metric name"):
            MetricsRegistry().counter(name)

    def test_invalid_and_reserved_label_names_rejected(self):
        r = MetricsRegistry()
        with pytest.raises(ValueError, match="invalid label name"):
            r.counter("smof_x_total", labelnames=("0bad",))
        with pytest.raises(ValueError, match="invalid label name"):
            r.counter("smof_y_total", labelnames=("__reserved",))
        with pytest.raises(ValueError, match="reserved"):
            r.histogram("smof_h_seconds", labelnames=("le",))

    def test_labels_resolve_one_child_per_combination(self):
        fam = MetricsRegistry().counter("smof_x_total", labelnames=("k",))
        fam.labels(k="a").inc()
        fam.labels(k="a").inc()
        fam.labels(k="b").inc(5)
        assert fam.labels(k="a").value == 2.0
        assert fam.labels(k="b").value == 5.0
        assert len(fam.children()) == 2
        with pytest.raises(ValueError, match="expected labels"):
            fam.labels(wrong="a")

    def test_labeled_family_refuses_labelless_proxy(self):
        fam = MetricsRegistry().counter("smof_x_total", labelnames=("k",))
        with pytest.raises(ValueError, match="call .labels"):
            fam.inc()

    def test_labelless_family_proxies_to_single_child(self):
        r = MetricsRegistry()
        r.counter("smof_c_total").inc(2)
        r.gauge("smof_g").set(7)
        r.histogram("smof_h_seconds").observe(1e-3)
        snap = r.snapshot()
        assert snap["smof_c_total"] == 2.0
        assert snap["smof_g"] == 7.0
        assert snap["smof_h_seconds_count"] == 1.0


# =============================================================================
# Exposition + the round-trip gate
# =============================================================================

def _full_registry() -> MetricsRegistry:
    r = MetricsRegistry()
    c = r.counter("smof_frames_total", "frames served", ("edge", "kind"))
    c.labels(edge="a->b", kind="evict").inc(3)
    c.labels(edge='we"ird\\path\nx', kind="restore").inc(1)
    r.gauge("smof_occupancy", "ring occupancy", ("edge",)) \
        .labels(edge="a->b").set(4)
    h = r.histogram("smof_latency_seconds", "per-request latency")
    for v in (1e-6, 3e-6, 1e-3, 0.5):
        h.observe(v)
    return r


class TestExposition:
    def test_escape_label_value(self):
        assert escape_label_value('a\\b"c\nd') == 'a\\\\b\\"c\\nd'

    def test_empty_registry_text_parses_to_nothing(self):
        assert parse_metrics_text(MetricsRegistry().metrics_text()) == {}

    def test_round_trip_preserves_every_sample(self):
        r = _full_registry()
        fams = parse_metrics_text(r.metrics_text())
        assert set(fams) == {"smof_frames_total", "smof_occupancy",
                             "smof_latency_seconds"}
        assert fams["smof_frames_total"]["type"] == "counter"
        assert fams["smof_frames_total"]["help"] == "frames served"
        # the parsed samples are exactly the snapshot, keys included —
        # escaped label values survive the round trip
        merged = {}
        for fam in fams.values():
            merged.update(fam["samples"])
        assert merged == r.snapshot()
        key = ('smof_frames_total{edge="we\\"ird\\\\path\\nx",'
               'kind="restore"}')
        assert merged[key] == 1.0

    def test_histogram_buckets_cumulative_and_inf_equals_count(self):
        fams = parse_metrics_text(_full_registry().metrics_text())
        s = fams["smof_latency_seconds"]["samples"]
        buckets = [(k, v) for k, v in s.items() if "_bucket{" in k]
        values = [v for _, v in buckets]
        assert values == sorted(values)          # cumulative
        assert s['smof_latency_seconds_bucket{le="+Inf"}'] == 4.0
        assert s["smof_latency_seconds_count"] == 4.0
        assert s["smof_latency_seconds_sum"] == pytest.approx(
            1e-6 + 3e-6 + 1e-3 + 0.5)

    def test_integer_values_render_without_trailing_zero(self):
        r = MetricsRegistry()
        r.counter("smof_n_total").inc(3)
        assert "smof_n_total 3\n" in r.metrics_text()

    def test_snapshot_delta_since_reports_what_moved(self):
        r = MetricsRegistry()
        c = r.counter("smof_a_total", labelnames=("k",))
        g = r.gauge("smof_g")
        c.labels(k="x").inc(2)
        g.set(1)
        before = r.snapshot()
        assert r.delta_since(before) == {}       # nothing moved
        c.labels(k="x").inc(3)
        c.labels(k="y").inc(1)                   # new sample counts from 0
        g.set(1)                                 # unchanged -> dropped
        assert r.delta_since(before) == {'smof_a_total{k="x"}': 3.0,
                                         'smof_a_total{k="y"}': 1.0}


class TestParserRejections:
    @pytest.mark.parametrize("text,msg", [
        ("smof_x_total 1\n", "no preceding # TYPE"),
        ("# TYPE smof_x_total counter\nsmof_x_total 1\nsmof_x_total 2\n",
         "duplicate sample"),
        ("# TYPE smof_x_total counter\n# TYPE smof_x_total counter\n",
         "duplicate TYPE"),
        ("# TYPE smof_x_total widget\n", "unknown type"),
        ("# TYPE smof_x_total counter\nsmof_x_total{k=\"a\\q\"} 1\n",
         r"bad escape"),
        ("# TYPE smof_x_total counter\nsmof_x_total{k=\"a} 1\n",
         "unterminated|malformed"),
        ("# TYPE smof_x_total counter\nsmof_x_total nan-ish\n",
         "non-numeric|malformed"),
        ("# TYPE smof_h histogram\n"
         'smof_h_bucket{le="1"} 5\nsmof_h_bucket{le="2"} 3\n'
         'smof_h_bucket{le="+Inf"} 5\nsmof_h_count 5\n',
         "not cumulative"),
        ("# TYPE smof_h histogram\n"
         'smof_h_bucket{le="1"} 2\nsmof_h_bucket{le="+Inf"} 2\n'
         "smof_h_count 3\n", "!= _count"),
        ("# TYPE smof_h histogram\n"
         'smof_h_bucket{le="1"} 2\nsmof_h_count 2\n', r"\+Inf"),
    ])
    def test_malformed_exposition_rejected(self, text, msg):
        with pytest.raises(ValueError, match=msg):
            parse_metrics_text(text)

    def test_plain_comments_and_blank_lines_ignored(self):
        fams = parse_metrics_text(
            "\n# just a comment\n# TYPE smof_x_total counter\n\n"
            "smof_x_total 1\n")
        assert fams["smof_x_total"]["samples"] == {"smof_x_total": 1.0}
