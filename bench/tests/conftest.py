"""The benchmark's own tests run on the CPU at small sizes:

    python -m pytest bench/tests

(``pytest.ini`` collects only ``tests/``, so the repository's suite does
not run these.)"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _compile_cache(tmp_path_factory, monkeypatch):
    """Keep the persistent compile cache of a test run out of the checkout."""
    from repro.runtime import compile_cache
    monkeypatch.setattr(compile_cache, "CACHE_DIR",
                        tmp_path_factory.getbasetemp() / "jax_cache")
