"""Suite-wide setup.

Property tests draw their examples deterministically (``derandomize``)
and keep no example database, so every run sees the same examples and a
test run writes nothing into the checkout.
"""
import importlib.util
import pathlib

import pytest
from hypothesis import settings

settings.register_profile("repro", derandomize=True, database=None)
settings.load_profile("repro")


@pytest.fixture(scope="session")
def chip_smoke():
    """The repository-root ``chip_smoke.py`` as a module."""
    path = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
