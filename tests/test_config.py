"""Central config flag registry (reference: common/ray_config_def.h +
RAY_<name> env overrides, ray_config.h:104)."""

import pytest

from ray_tpu.core.config import RayTpuConfig, _REGISTRY, cfg


def test_defaults_and_registry():
    c = RayTpuConfig()
    assert c.object_store_capacity_bytes == 8 << 30
    assert c.native_store is False
    assert c.inline_max_bytes == 100 * 1024
    # every flag is typed + documented
    for flag in _REGISTRY.values():
        assert flag.doc
        assert isinstance(flag.default, flag.type)


def test_env_override(monkeypatch):
    c = RayTpuConfig()
    monkeypatch.setenv("RAY_TPU_OBJECT_STORE_CAPACITY_BYTES", "1e6")
    assert c.object_store_capacity_bytes == 1_000_000
    monkeypatch.setenv("RAY_TPU_NATIVE_STORE", "true")
    assert c.native_store is True
    monkeypatch.setenv("RAY_TPU_NATIVE_STORE", "off")
    assert c.native_store is False
    # unknown tokens degrade to truthy-with-warning, not a crash at init
    monkeypatch.setenv("RAY_TPU_NATIVE_STORE", "bogus")
    assert c.native_store is True


def test_set_overrides_beat_env(monkeypatch):
    c = RayTpuConfig()
    monkeypatch.setenv("RAY_TPU_HEALTH_CHECK_FAILURES", "7")
    assert c.health_check_failures == 7
    c.set(health_check_failures=2)
    assert c.health_check_failures == 2
    c.reset("health_check_failures")
    assert c.health_check_failures == 7


def test_unknown_flag_rejected():
    c = RayTpuConfig()
    with pytest.raises(ValueError, match="unknown config flag"):
        c.set(definitely_not_a_flag=1)
    with pytest.raises(AttributeError):
        _ = c.definitely_not_a_flag


def test_type_coercion_and_mismatch():
    c = RayTpuConfig()
    c.set(gcs_snapshot_interval_s=2)  # int ok where float expected
    assert c.gcs_snapshot_interval_s == 2.0
    with pytest.raises(ValueError, match="expects"):
        c.set(max_process_workers="not-a-number")
    c.reset()


def test_describe_lists_every_flag():
    text = cfg.describe()
    for name in _REGISTRY:
        assert name in text


def test_store_reads_flags(monkeypatch, tmp_path):
    monkeypatch.setenv("RAY_TPU_INLINE_MAX_BYTES", "10")
    from ray_tpu.core.ids import JobID, ObjectID
    from ray_tpu.core.object_store import ObjectStore, Tier

    store = ObjectStore()
    oid = ObjectID.for_put(JobID.next())
    store.put(oid, b"x" * 100)  # > 10 bytes -> host tier, not inline
    assert store.entry(oid).tier == Tier.HOST


def test_compile_cache_is_placed_from_outside_or_at_one_fixed_path(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: nothing is set in code. Unset: the
    one fixed path inside the checkout, exported for jax and children."""
    import os

    import jax

    from ray_tpu.core import compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV_VAR, "/somewhere/outside")
    assert compile_cache.ensure_compile_cache() == "/somewhere/outside"
    assert jax.config.jax_compilation_cache_dir == before  # untouched
    assert os.environ[compile_cache.ENV_VAR] == "/somewhere/outside"

    monkeypatch.delenv(compile_cache.ENV_VAR)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    fixed = os.path.join(repo, ".jax_cache")
    try:
        assert compile_cache.ensure_compile_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
        assert os.environ[compile_cache.ENV_VAR] == fixed
        assert compile_cache.ensure_compile_cache() == fixed  # and stays there
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
