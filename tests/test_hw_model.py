"""The hardware table: rows keyed by ``device_kind``, each with its source."""
from types import SimpleNamespace

import jax
import pytest

from repro.kernels.hw_model import HARDWARE, TPU_V5E, hardware_for


def test_v5e_row_carries_its_published_source():
    row = HARDWARE["TPU v5 lite"]
    assert row is TPU_V5E
    assert "TPU v5e" in row.source and "Google Cloud" in row.source
    assert (row.peak_flops, row.hbm_bw, row.hbm_bytes) == (
        197e12, 819e9, 16 * 2**30)


def test_every_row_is_keyed_by_its_own_kind():
    for kind, row in HARDWARE.items():
        assert row.kind == kind and row.source


@pytest.mark.parametrize("kind", ["TPU v4", "TPU v99 lite", ""])
def test_unknown_tpu_kind_raises(kind):
    dev = SimpleNamespace(platform="tpu", device_kind=kind)
    with pytest.raises(ValueError, match="no hardware row"):
        hardware_for(dev)


def test_known_tpu_kind_selects_its_row():
    dev = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert hardware_for(dev) is TPU_V5E


def test_cpu_prices_for_v5e_by_name():
    assert jax.devices()[0].platform == "cpu"
    assert hardware_for() is TPU_V5E


def test_other_platforms_raise():
    with pytest.raises(ValueError, match="no hardware model"):
        hardware_for(SimpleNamespace(platform="gpu", device_kind="H100"))
