"""Config (CLI/env precedence, validation) and metrics (counters,
Prometheus export) tests, mirroring the reference's config validation
(`config.rs:435-454`), env-var surface (`config.rs:174-340`), metric export
(`metrics.rs:233-310`) and the counter invariant suite
(`metrics.rs:383-411`, `tests/metrics_test.rs`, `tests/denied_keys_test.rs`).
"""

import pytest

from throttlecrab_tpu.server.config import Config, ConfigError
from throttlecrab_tpu.server.metrics import (
    MAX_KEY_LENGTH,
    METRIC_NAMES,
    STAGE_CLOCKS,
    Metrics,
    TopDeniedKeys,
    escape_label_value,
)

# ----------------------------------------------------------------- config #


def test_defaults_match_reference():
    cfg = Config.from_env_and_args(["--http"])
    assert cfg.http_port == 8080
    assert cfg.grpc_port == 8070
    assert cfg.redis_port == 6379
    assert cfg.store == "periodic"
    assert cfg.store_capacity == 100_000
    assert cfg.store_cleanup_interval == 300
    assert cfg.store_cleanup_probability == 10_000
    assert cfg.store_min_interval == 5
    assert cfg.store_max_interval == 300
    assert cfg.store_max_operations == 1_000_000
    assert cfg.buffer_size == 100_000
    assert cfg.max_denied_keys == 100
    assert cfg.log_level == "info"


def test_requires_at_least_one_transport():
    with pytest.raises((ConfigError, SystemExit)):
        Config.from_env_and_args([])


def test_env_fallback_and_cli_precedence(monkeypatch):
    monkeypatch.setenv("THROTTLECRAB_HTTP", "true")
    monkeypatch.setenv("THROTTLECRAB_HTTP_PORT", "9999")
    monkeypatch.setenv("THROTTLECRAB_STORE", "adaptive")
    cfg = Config.from_env_and_args([])
    assert cfg.http is True
    assert cfg.http_port == 9999
    assert cfg.store == "adaptive"
    # CLI wins over env (config.rs:356-361).
    cfg = Config.from_env_and_args(["--http-port", "1234"])
    assert cfg.http_port == 1234


def test_invalid_store_rejected():
    with pytest.raises(ConfigError):
        Config.from_env_and_args(["--http", "--store", "bogus"])


def test_max_denied_keys_range():
    with pytest.raises(ConfigError):
        Config.from_env_and_args(["--http", "--max-denied-keys", "20000"])


def test_list_env_vars_exits_zero(capsys):
    with pytest.raises(SystemExit) as excinfo:
        Config.from_env_and_args(["--list-env-vars"])
    assert excinfo.value.code == 0
    out = capsys.readouterr().out
    assert "THROTTLECRAB_HTTP_PORT" in out
    assert "THROTTLECRAB_STORE_CLEANUP_INTERVAL" in out


def test_tpu_knobs():
    cfg = Config.from_env_and_args(
        ["--http", "--batch-size", "512", "--shards", "4",
         "--keymap", "python"]
    )
    assert cfg.batch_size == 512
    assert cfg.shards == 4
    with pytest.raises(ConfigError):
        Config.from_env_and_args(["--http", "--keymap", "rust"])
    with pytest.raises(ConfigError):
        Config.from_env_and_args(["--http", "--shards", "0"])


# ---------------------------------------------------------------- metrics #


def test_counter_invariant():
    """allowed + denied + errors == total (metrics.rs:383-411)."""
    m = Metrics()
    for i in range(10):
        m.record_request("http", allowed=i % 3 != 0)
    m.record_error("redis")
    assert (
        m.requests_allowed + m.requests_denied + m.requests_errors
        == m.requests_total
    )


def test_prometheus_export_names():
    m = Metrics(max_denied_keys=5)
    m.record_request_with_key("http", False, "bad-key")
    text = m.export_prometheus()
    for name in (
        "throttlecrab_uptime_seconds",
        "throttlecrab_requests_total",
        "throttlecrab_requests_by_transport",
        "throttlecrab_requests_allowed",
        "throttlecrab_requests_denied",
        "throttlecrab_requests_errors",
        "throttlecrab_top_denied_keys",
        "throttlecrab_tpu_device_launches",
        "throttlecrab_tpu_expired_hits",
    ):
        assert name in text, name
    assert 'throttlecrab_top_denied_keys{key="bad-key",rank="1"} 1' in text


@pytest.mark.parametrize("clock", STAGE_CLOCKS, ids=lambda c: c.name)
def test_stage_clock_exports_as_an_unlabelled_counter(clock):
    """Each host stage clock is a registered counter on /metrics, one
    unlabelled sample that carries what its threads added."""

    def sample():
        text = Metrics().export_prometheus()
        assert f"# TYPE {clock.name} counter" in text
        values = [
            float(line.partition(" ")[2])
            for line in text.splitlines()
            if line.partition(" ")[0] == clock.name
        ]
        assert len(values) == 1
        return values[0]

    assert clock.name in METRIC_NAMES
    before = sample()
    clock.add_ns(250_000_000)
    # A collection in between may add to the GC clock, never take away.
    assert before + 0.25 <= sample() < before + 1.25


def test_cluster_metrics_export():
    """Elastic-cluster surfaces: per-peer breaker/migration counters
    and the epoch/replica/takeover gauges, exported exactly when the
    providers are wired (cluster deployments only)."""
    m = Metrics()
    base = m.export_prometheus()
    assert "throttlecrab_cluster_epoch" not in base
    m.set_cluster_stats_provider(lambda: {
        "10.0.0.1:9" : {"forwarded": 7, "failed": 2, "breaker_open": 1,
                        "migrated_keys": 40},
    })
    m.set_cluster_view_provider(lambda: {
        "epoch": 3, "migrated_in": 12, "replica_rows": 5, "takeovers": 1,
    })
    text = m.export_prometheus()
    assert 'throttlecrab_cluster_forwarded_total{peer="10.0.0.1:9"} 7' in text
    assert 'throttlecrab_cluster_breaker_open{peer="10.0.0.1:9"} 1' in text
    assert 'throttlecrab_cluster_migrated_keys{peer="10.0.0.1:9"} 40' in text
    assert "throttlecrab_cluster_epoch 3" in text
    assert "throttlecrab_cluster_migrated_in_total 12" in text
    assert "throttlecrab_cluster_replica_rows 5" in text
    assert "throttlecrab_cluster_takeovers_total 1" in text


def test_cluster_config_knobs_validate():
    from throttlecrab_tpu.server.config import Config, ConfigError

    cfg = Config(http=True)
    assert cfg.cluster_vnodes == 128 and cfg.cluster_replicate is True
    cfg.validate()
    cfg.cluster_vnodes = 0  # legacy kill switch is a valid setting
    cfg.validate()
    cfg.cluster_vnodes = -1
    with pytest.raises(ConfigError):
        cfg.validate()
    cfg.cluster_vnodes = 128
    cfg.cluster_handoff_timeout_ms = 0
    with pytest.raises(ConfigError):
        cfg.validate()


def test_top_denied_keys_ranking_and_caps():
    """denied_keys_test.rs: ranking by count, prune at 3x, key-length cap."""
    t = TopDeniedKeys(max_keys=3)
    for key, n in [("a", 5), ("b", 3), ("c", 8), ("d", 1)]:
        for _ in range(n):
            t.record(key)
    top = t.top()
    assert [k for k, _ in top] == ["c", "a", "b"]

    long_key = "x" * 1000
    t.record(long_key)
    assert all(len(k) <= MAX_KEY_LENGTH for k in t.counts)

    # Grow-then-prune: more than 3x max_keys distinct keys triggers prune.
    t2 = TopDeniedKeys(max_keys=2)
    for i in range(10):
        for _ in range(i + 1):
            t2.record(f"k{i}")
    assert len(t2.counts) <= 6
    assert [k for k, _ in t2.top()] == ["k9", "k8"]


def test_top_denied_disabled_at_zero():
    m = Metrics(max_denied_keys=0)
    m.record_request_with_key("http", False, "k")
    assert m.top_denied is None
    assert "throttlecrab_top_denied_keys" not in m.export_prometheus()


def test_label_escaping():
    assert escape_label_value('a"b\\c\nd') == 'a\\"b\\\\c\\nd'
    m = Metrics(max_denied_keys=2)
    m.record_request_with_key("http", False, 'key"with\nstuff')
    text = m.export_prometheus()
    assert 'key="key\\"with\\nstuff"' in text
