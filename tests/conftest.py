import importlib.util
import os
import pathlib

import pytest

# Tests run against the single host CPU device (the dry-run, and ONLY the
# dry-run, forces 512 placeholder devices).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# Property tests prefer the real hypothesis (pip install -e .[test]); in
# offline containers without it, fall back to the seeded sampler so the five
# hypothesis-based modules still collect and run.
try:
    import hypothesis  # noqa: F401
except ImportError:
    import _hypothesis_fallback

    _hypothesis_fallback.install()

import jax

jax.config.update("jax_enable_x64", False)


@pytest.fixture(scope="session")
def chip_smoke():
    """The repo-root ``chip_smoke.py`` as a module; importing it touches no
    device, only ``main()`` does."""
    path = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
