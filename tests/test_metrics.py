"""Metrics layer: registry naming/exposition and client query semantics."""

import math

import pytest

from karpenter_tpu.api.horizontalautoscaler import (
    Metric,
    MetricTarget,
    PrometheusMetricSource,
)
from karpenter_tpu.metrics.clients import (
    MetricQueryError,
    MetricsClientFactory,
    RegistryMetricsClient,
    parse_instant_selector,
)
from karpenter_tpu.metrics.registry import GaugeRegistry


def metric_for(query):
    return Metric(
        prometheus=PrometheusMetricSource(
            query=query, target=MetricTarget(type="AverageValue", value=1)
        )
    )


class TestSelectorParsing:
    def test_bare_name(self):
        assert parse_instant_selector("karpenter_queue_length") == (
            "karpenter_queue_length",
            {},
        )

    def test_labels(self):
        name, labels = parse_instant_selector(
            'karpenter_queue_length{name="q", namespace="default"}'
        )
        assert name == "karpenter_queue_length"
        assert labels == {"name": "q", "namespace": "default"}

    @pytest.mark.parametrize(
        "bad",
        [
            "sum(rate(foo[5m]))",  # full PromQL unsupported
            'foo{name="a" other="b"}',  # missing comma: must error, not drop
            'foo{name=}',
            "foo{,}",
            "",
        ],
    )
    def test_bad_syntax_raises(self, bad):
        with pytest.raises(MetricQueryError):
            parse_instant_selector(bad)


class TestRegistryClient:
    def test_reads_gauge(self):
        registry = GaugeRegistry()
        registry.register("queue", "length").set("q", "default", 41.0)
        client = RegistryMetricsClient(registry)
        got = client.get_current_value(
            metric_for('karpenter_queue_length{name="q"}')
        )
        assert got.value == 41.0

    def test_instant_vector_of_one_enforced(self):
        """reference: prometheus.go:46-55"""
        registry = GaugeRegistry()
        vec = registry.register("queue", "length")
        client = RegistryMetricsClient(registry)
        spec = metric_for("karpenter_queue_length")
        with pytest.raises(MetricQueryError, match="got 0 series"):
            client.get_current_value(spec)
        vec.set("a", "default", 1.0)
        vec.set("b", "default", 2.0)
        with pytest.raises(MetricQueryError, match="got 2 series"):
            client.get_current_value(spec)

    def test_unknown_metric_name(self):
        client = RegistryMetricsClient(GaugeRegistry())
        with pytest.raises(MetricQueryError, match="no metric named"):
            client.get_current_value(metric_for('nope{name="q"}'))


class TestFactory:
    def test_prometheus_source_dispatch(self):
        factory = MetricsClientFactory(registry=GaugeRegistry())
        client = factory.for_metric(metric_for("foo"))
        assert isinstance(client, RegistryMetricsClient)


class TestExposition:
    def test_text_format_with_nan(self):
        registry = GaugeRegistry()
        registry.register("reserved_capacity", "cpu_utilization").set(
            "g", "default", math.nan
        )
        text = registry.expose_text()
        assert "# TYPE karpenter_reserved_capacity_cpu_utilization gauge" in text
        assert (
            'karpenter_reserved_capacity_cpu_utilization{name="g",namespace="default"} NaN'
            in text
        )


class TestRuntimeSelfMetrics:
    def test_manager_publishes_tick_and_reconcile_counts(self):
        from karpenter_tpu.api.core import ObjectMeta
        from karpenter_tpu.api.scalablenodegroup import (
            ScalableNodeGroup,
            ScalableNodeGroupSpec,
        )
        from karpenter_tpu.cloudprovider.fake import FakeFactory
        from karpenter_tpu.runtime import KarpenterRuntime

        provider = FakeFactory()
        provider.node_replicas["g"] = 1
        rt = KarpenterRuntime(cloud_provider_factory=provider)
        rt.store.create(
            ScalableNodeGroup(
                metadata=ObjectMeta(name="g"),
                spec=ScalableNodeGroupSpec(
                    replicas=1, type="FakeNodeGroup", id="g"
                ),
            )
        )
        rt.manager.reconcile_all()
        reg = rt.registry
        assert reg.gauge("runtime", "tick_seconds").get(
            "manager", "-"
        ) is not None
        assert reg.gauge("runtime", "reconciles_total").get(
            "ScalableNodeGroup", "-"
        ) == 1.0
        assert reg.gauge("runtime", "reconcile_errors_total").get(
            "ScalableNodeGroup", "-"
        ) in (None, 0.0)
        # counters expose the Prometheus counter TYPE, not gauge
        text = reg.expose_text()
        assert "# TYPE karpenter_runtime_reconciles_total counter" in text

    def test_encode_cache_counters(self):
        from karpenter_tpu.api.core import (
            Container,
            Node,
            NodeCondition,
            NodeStatus,
            ObjectMeta,
            Pod,
            PodSpec,
        )
        from karpenter_tpu.api.metricsproducer import (
            MetricsProducer,
            MetricsProducerSpec,
            PendingCapacitySpec,
        )
        from karpenter_tpu.metrics.producers.pendingcapacity import (
            group_profile,
            solve_pending,
        )
        from karpenter_tpu.store import Store
        from karpenter_tpu.store.columnar import PendingFeed
        from karpenter_tpu.utils.quantity import Quantity

        store = Store()
        feed = PendingFeed(store, group_profile)
        store.create(
            Node(
                metadata=ObjectMeta(name="n", labels={"g": "a"}),
                status=NodeStatus(
                    allocatable={"cpu": Quantity.parse("8")},
                    conditions=[NodeCondition(type="Ready", status="True")],
                ),
            )
        )
        store.create(
            Pod(
                metadata=ObjectMeta(name="p"),
                spec=PodSpec(
                    containers=[
                        Container(requests={"cpu": Quantity.parse("1")})
                    ]
                ),
            )
        )
        mp = store.create(
            MetricsProducer(
                metadata=ObjectMeta(name="mp"),
                spec=MetricsProducerSpec(
                    pending_capacity=PendingCapacitySpec(
                        node_selector={"g": "a"}
                    )
                ),
            )
        )
        registry = GaugeRegistry()
        solve_pending(store, [mp], registry, feed=feed)
        solve_pending(store, [mp], registry, feed=feed)
        gauge = registry.gauge("runtime", "encode_cache_total")
        assert gauge.get("miss", "-") == 1.0
        assert gauge.get("hit", "-") == 1.0


class TestMetricsDocDrift:
    """Doc-drift lint (extends the exposition-lint suite): every
    `karpenter_*` family registered in code must appear in
    docs/OPERATIONS.md's "Metrics reference" table, and every
    documented family must still exist in code — PR 10/11 both shipped
    frozen-series/undocumented-gauge bugs this would have caught. Also
    enforces the unit-suffix discipline: `_seconds`/`_ms`/`_bytes`
    families must declare the matching unit, `_total` families must be
    counters with unit "count"."""

    # families registered through data-driven loops the AST scanner
    # cannot resolve (each pointer names the loop)
    EXPLICIT_FAMILIES = {
        # pendingcapacity/__init__.register_gauges: for name in (...)
        "karpenter_pending_capacity_pending_pods": "gauge",
        "karpenter_pending_capacity_additional_nodes_needed": "gauge",
        "karpenter_pending_capacity_lp_lower_bound": "gauge",
        "karpenter_pending_capacity_unschedulable_pods": "gauge",
    }
    # families whose NAME is dynamic (documented as a pattern row)
    DYNAMIC_PREFIXES = (
        # reservedcapacity.register_gauges: f"{resource}_{metric_type}"
        "karpenter_reserved_capacity_",
    )

    @staticmethod
    def _module_constants(tree):
        import ast

        consts = {}
        for node in tree.body:
            if isinstance(node, ast.Assign) and len(node.targets) == 1:
                target = node.targets[0]
                if (
                    isinstance(target, ast.Name)
                    and isinstance(node.value, ast.Constant)
                    and isinstance(node.value.value, str)
                ):
                    consts[target.id] = node.value.value
        return consts

    def _scan_code_families(self):
        """AST scan of karpenter_tpu/ for `<registry>.register(sub,
        name, kind=...)` calls (incl. the `reg = registry.register`
        alias), resolving literal args and module-level string
        constants."""
        import ast
        import os

        root_dir = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "karpenter_tpu",
        )
        families = dict(self.EXPLICIT_FAMILIES)
        for root, dirs, files in os.walk(root_dir):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for file_name in files:
                if not file_name.endswith(".py"):
                    continue
                path = os.path.join(root, file_name)
                tree = ast.parse(open(path).read())
                consts = self._module_constants(tree)

                def resolve(arg):
                    if isinstance(arg, ast.Constant) and isinstance(
                        arg.value, str
                    ):
                        return arg.value
                    if isinstance(arg, ast.Name):
                        return consts.get(arg.id)
                    return None

                for node in ast.walk(tree):
                    if not isinstance(node, ast.Call):
                        continue
                    is_register = (
                        isinstance(node.func, ast.Attribute)
                        and node.func.attr == "register"
                    ) or (
                        isinstance(node.func, ast.Name)
                        and node.func.id == "reg"
                    )
                    if not is_register or len(node.args) < 2:
                        continue
                    sub = resolve(node.args[0])
                    name = resolve(node.args[1])
                    if sub is None or name is None:
                        continue  # not a metric register / dynamic name
                    kind = "gauge"
                    for kw in node.keywords:
                        if kw.arg == "kind" and isinstance(
                            kw.value, ast.Constant
                        ):
                            kind = kw.value.value
                    families[f"karpenter_{sub}_{name}"] = kind
        return families

    def _doc_rows(self):
        """(family, kind, unit) rows of the OPERATIONS.md table;
        pattern rows keep their `<...>` placeholders."""
        import os
        import re

        path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "docs", "OPERATIONS.md",
        )
        text = open(path).read()
        section = text.split("## Metrics reference", 1)
        assert len(section) == 2, (
            "docs/OPERATIONS.md must carry the 'Metrics reference' table"
        )
        body = section[1].split("\n## ", 1)[0]
        rows = []
        for match in re.finditer(
            r"^\| `(karpenter_[^`]+)` \| (\w+) \| ([^|]+) \|",
            body, re.MULTILINE,
        ):
            rows.append((
                match.group(1), match.group(2), match.group(3).strip()
            ))
        assert rows, "the Metrics reference table parsed empty"
        return rows

    def test_every_code_family_is_documented(self):
        code = self._scan_code_families()
        documented = {family for family, _k, _u in self._doc_rows()}
        missing = {
            family for family in code
            if family not in documented
            and not family.startswith(self.DYNAMIC_PREFIXES)
        }
        assert not missing, (
            f"registered but undocumented in docs/OPERATIONS.md "
            f"'Metrics reference': {sorted(missing)}"
        )

    def test_every_documented_family_exists_in_code(self):
        code = self._scan_code_families()
        stale = {
            family for family, _k, _u in self._doc_rows()
            if "<" not in family  # pattern rows match by prefix
            and family not in code
        }
        assert not stale, (
            f"documented in docs/OPERATIONS.md but not registered "
            f"anywhere in code: {sorted(stale)}"
        )
        # every pattern row's prefix must correspond to a known
        # dynamic-name registration
        patterns = [
            family for family, _k, _u in self._doc_rows()
            if "<" in family
        ]
        for pattern in patterns:
            prefix = pattern.split("<", 1)[0]
            assert prefix in self.DYNAMIC_PREFIXES, (
                f"pattern row {pattern} has no dynamic registration"
            )

    def test_kinds_and_unit_suffixes_agree(self):
        code = self._scan_code_families()
        for family, kind, unit in self._doc_rows():
            if "<" in family:
                continue
            assert kind == code[family], (
                f"{family}: documented as {kind}, registered as "
                f"{code[family]}"
            )
            if family.endswith("_total"):
                assert kind == "counter" and unit == "count", (
                    f"{family}: _total families are counters with "
                    f"unit 'count' (doc says {kind}/{unit})"
                )
            elif family.endswith("_seconds"):
                assert unit == "seconds", (
                    f"{family}: _seconds family documented as {unit}"
                )
            elif family.endswith("_ms"):
                assert unit == "ms", (
                    f"{family}: _ms family documented as {unit}"
                )
            elif family.endswith("_bytes"):
                assert unit == "bytes", (
                    f"{family}: _bytes family documented as {unit}"
                )
        # the reverse unit audit: any family documented with a time
        # unit must carry the matching suffix — the ms-vs-seconds
        # dashboard trap the PR 9 migration note warned about
        for family, _kind, unit in self._doc_rows():
            if "<" in family:
                continue
            if unit == "seconds":
                assert family.endswith("_seconds"), (
                    f"{family}: seconds-valued family must carry the "
                    f"_seconds suffix"
                )
            if unit == "ms":
                assert family.endswith("_ms"), (
                    f"{family}: millisecond-valued family must carry "
                    f"the _ms suffix"
                )


class TestHistogramPercentile:
    """HistogramVec.percentile — the estimator behind the simulator
    report's provisioning-lead p50/p99 columns —
    must apply Prometheus's histogram_quantile() semantics: linear
    interpolation within the bucket holding the rank, clamp-to-bound
    beyond the last finite bucket, None for an empty series."""

    def _hist(self):
        from karpenter_tpu.metrics.registry import GaugeRegistry

        registry = GaugeRegistry()
        return registry.register(
            "lead", "seconds", kind="histogram",
            buckets=(0.1, 1.0, 10.0),
        )

    def test_empty_series_is_none(self):
        hist = self._hist()
        assert hist.percentile("g", "default", 50) is None
        assert hist.percentile("missing", "default", 99) is None

    def test_linear_within_bucket_matches_prometheus(self):
        hist = self._hist()
        for _ in range(4):
            hist.observe("g", "default", 0.05)  # bucket (0, 0.1]
        for _ in range(4):
            hist.observe("g", "default", 5.0)  # bucket (1.0, 10.0]
        # rank 4 of 8 lands exactly at the first bucket's upper bound
        assert hist.percentile("g", "default", 50) == pytest.approx(0.1)
        # rank 6 sits halfway through the (1.0, 10.0] bucket's 4 samples
        assert hist.percentile("g", "default", 75) == pytest.approx(5.5)

    def test_overflow_clamps_to_last_finite_bound(self):
        hist = self._hist()
        for _ in range(10):
            hist.observe("g", "default", 100.0)  # all +Inf bucket
        assert hist.percentile("g", "default", 99) == pytest.approx(10.0)
