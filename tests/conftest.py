import warnings

import pytest

warnings.filterwarnings("ignore")


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: slow tests (subprocess compiles)")


@pytest.fixture
def band(monkeypatch):
    """Force the fused loop to run certified at a given band (default: the
    TPU's), as on a device whose float64 is not IEEE."""
    from repro.core import fused

    def use(value=fused.TPU_BAND):
        monkeypatch.setattr(fused, "device_band", lambda: value)
        fused.reset_dispatch_count()
    use()
    return use
