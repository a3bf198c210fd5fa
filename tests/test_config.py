"""Typed options groups + validators (reference: Options classes bound via
MS.Options with IConfigurationValidator passes — NonSilo.Tests'
builder/config unit-test tier)."""

import logging

import pytest

from orleans_tpu.config import (
    ClusterOptions,
    DirectoryOptions,
    GrainCollectionOptions,
    MembershipOptions,
    MessagingOptions,
    SchedulingOptions,
    apply_options,
    flatten,
    log_options,
    validate_options,
)
from orleans_tpu.core.errors import ConfigurationError
from orleans_tpu.runtime import SiloBuilder


class TestValidators:
    def test_defaults_all_valid(self):
        validate_options(ClusterOptions(), MessagingOptions(),
                         SchedulingOptions(), GrainCollectionOptions(),
                         MembershipOptions(), DirectoryOptions())

    def test_nonpositive_rejected(self):
        with pytest.raises(ConfigurationError, match="response_timeout"):
            MessagingOptions(response_timeout=0).validate()
        with pytest.raises(ConfigurationError, match="cache_size"):
            DirectoryOptions(cache_size=-1).validate()

    def test_cross_field_rules(self):
        with pytest.raises(ConfigurationError, match="collection_age"):
            GrainCollectionOptions(collection_age=10,
                                   collection_quantum=60).validate()
        with pytest.raises(ConfigurationError, match="never be reached"):
            MembershipOptions(votes_needed=5, num_probed=2).validate()
        with pytest.raises(ConfigurationError, match="non-empty"):
            ClusterOptions(cluster_id="").validate()


class TestFlatten:
    def test_flatten_overlays_groups(self):
        cfg = flatten(MessagingOptions(response_timeout=7.5),
                      MembershipOptions(probe_period=0.25),
                      name="s1")
        assert cfg.name == "s1"
        assert cfg.response_timeout == 7.5
        assert cfg.membership_probe_period == 0.25
        # untouched groups keep SiloConfig defaults
        assert cfg.collection_quantum == 60.0

    def test_flatten_validates(self):
        with pytest.raises(ConfigurationError):
            flatten(MessagingOptions(response_timeout=-1))

    def test_apply_options_on_existing_config(self):
        from orleans_tpu.runtime.silo import SiloConfig
        cfg = SiloConfig(name="x")
        apply_options(cfg, SchedulingOptions(detect_deadlocks=True,
                                             turn_warning_length=0.5))
        assert cfg.detect_deadlocks is True
        assert cfg.turn_warning_length == 0.5


class TestBuilderIntegration:
    def test_with_options(self):
        b = (SiloBuilder().with_name("opt-silo")
             .with_options(MessagingOptions(response_timeout=3.0),
                           GrainCollectionOptions(collection_age=120,
                                                  collection_quantum=30)))
        assert b.config.response_timeout == 3.0
        assert b.config.collection_age == 120

    def test_with_options_rejects_invalid(self):
        with pytest.raises(ConfigurationError):
            SiloBuilder().with_options(MembershipOptions(num_probed=0))

    def test_cluster_identity_flows_to_config(self):
        b = SiloBuilder().with_options(
            ClusterOptions(cluster_id="prod", service_id="svc1"))
        assert b.config.cluster_id == "prod"
        assert b.config.service_id == "svc1"

    def test_unconsumed_group_rejected_not_dropped(self):
        from orleans_tpu.config import DispatchOptions
        with pytest.raises(ConfigurationError, match="VectorRuntime"):
            SiloBuilder().with_options(DispatchOptions(capacity_per_shard=4))

    def test_dispatch_options_consumed_by_vector_runtime(self):
        from orleans_tpu.config import DispatchOptions
        from orleans_tpu.dispatch import VectorRuntime
        from orleans_tpu.parallel import make_mesh
        rt = VectorRuntime(mesh=make_mesh(1),
                           options=DispatchOptions(capacity_per_shard=64))
        assert rt.capacity_per_shard == 64


@pytest.mark.parametrize("gone", ["batched_ingress", "batched_egress"])
def test_deleted_path_options_are_rejected_by_name(gone):
    """The per-frame ingress pump and the per-message response path are
    gone with their switches: an old deployment file that still sets one
    must fail loudly, naming it, on every way in — never be ignored."""
    from orleans_tpu.runtime.silo import SiloConfig
    with pytest.raises(TypeError, match=gone):
        SiloConfig(**{gone: False})
    with pytest.raises(AttributeError, match=gone):
        SiloBuilder().with_config(**{gone: False})
    with pytest.raises(TypeError, match=gone):
        MessagingOptions(**{gone: False})
    assert not hasattr(flatten(MessagingOptions()), gone)


def test_the_tick_lever_has_one_field_and_one_default():
    """``offloop_tick`` is the one old-path switch left (PERF.md section
    6, PR 30). It is a silo option with one default, the served path;
    ``DispatchOptions`` no longer carries a second copy with the
    opposite default, so a bare runtime ticks on its worker."""
    from orleans_tpu.config import DispatchOptions
    from orleans_tpu.dispatch import VectorRuntime
    from orleans_tpu.parallel import make_mesh
    from orleans_tpu.runtime.silo import SiloConfig
    with pytest.raises(TypeError, match="offloop_tick"):
        DispatchOptions(offloop_tick=True)
    assert SiloConfig().offloop_tick is True
    assert flatten(MessagingOptions()).offloop_tick is True
    assert flatten(MessagingOptions(offloop_tick=False)).offloop_tick is False
    rt = VectorRuntime(mesh=make_mesh(1),
                       options=DispatchOptions(capacity_per_shard=8))
    assert rt.offloop_tick is True
    assert not hasattr(VectorRuntime, "_run_batch")


def test_dispatch_imports_nothing_from_the_loop_profiler():
    """Layering: the device tier is observed, it does not reach up into
    an observer. ``VectorRuntime.loop_prof`` is injected by the silo;
    no module under ``orleans_tpu/dispatch`` imports
    ``observability.profiling``."""
    import ast
    import pathlib

    import orleans_tpu.dispatch as pkg
    root = pathlib.Path(pkg.__file__).parent
    files = sorted(root.rglob("*.py"))
    assert len(files) > 5
    bad = []
    for f in files:
        for node in ast.walk(ast.parse(f.read_text())):
            if isinstance(node, ast.ImportFrom):
                names = [node.module or ""] + [
                    f"{node.module or ''}.{a.name}" for a in node.names]
            elif isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            else:
                continue
            if any(n.endswith("observability.profiling") for n in names):
                bad.append((f.name, node.lineno))
    assert not bad, bad


def test_log_options_dumps_every_field(caplog):
    with caplog.at_level(logging.INFO, logger="orleans.options"):
        log_options(MessagingOptions(), MembershipOptions())
    text = caplog.text
    assert "MessagingOptions.response_timeout" in text
    assert "MembershipOptions.votes_needed" in text
