"""Generic DDS fuzz: every channel type through the same engine.

Mirrors packages/dds/test-dds-utils ddsFuzzHarness: seeded action
mixes, partial sequencing, reconnect churn, convergence asserts —
parametrized over the whole channel catalogue.
"""
import pytest

from fluidframework_tpu.testing.dds_fuzz import (
    ACTIONS,
    DdsFuzzConfig,
    run_dds_fuzz,
)

CHANNELS = sorted(ACTIONS)


@pytest.mark.parametrize("channel_type", CHANNELS)
@pytest.mark.parametrize("seed", [0, 1])
def test_dds_fuzz_converges(channel_type, seed):
    report = run_dds_fuzz(DdsFuzzConfig(
        channel_type=channel_type, seed=seed, n_steps=220,
    ))
    assert report.actions > 30, (
        f"{channel_type} generator produced too few actions"
    )


@pytest.mark.parametrize("channel_type", ["sharedstring", "sharedmap",
                                          "sharedmatrix"])
def test_dds_fuzz_heavy_churn(channel_type):
    """Higher fault pressure on the structurally hardest DDSes."""
    report = run_dds_fuzz(DdsFuzzConfig(
        channel_type=channel_type, seed=99, n_steps=350,
        p_reconnect_churn=0.06, reconnect_after=8,
    ))
    assert report.reconnects > 0


@pytest.mark.parametrize("seed", range(8))
def test_sticky_intervals_fuzz_with_zamboni(seed):
    """Stickiness x compaction x churn (mirrors
    intervalCollection.fuzz.spec.ts): randomized endpoint stickiness
    rides the string mix, with in-run zamboni interleavings; the
    convergence signature includes resolved sticky endpoints, so any
    endpoint drift across clients or across compaction fails here."""
    report = run_dds_fuzz(DdsFuzzConfig(
        channel_type="sharedstring", seed=7000 + seed, n_steps=300,
    ))
    sticky_adds = [t for t in report.trace if "iv add" in t]
    zambonis = [t for t in report.trace if "zamboni" in t]
    assert sticky_adds, "mix never added an interval"
    assert zambonis, "mix never ran zamboni"
    modes = {t.rsplit(" ", 1)[1] for t in sticky_adds}
    assert len(modes) >= 3, f"stickiness modes too narrow: {modes}"
