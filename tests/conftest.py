"""Test configuration.

Tests run on CPU with 8 virtual devices so multi-chip sharding paths
(`shard_map` over a Mesh) are exercised without TPU hardware — the
JAX-native "fake cluster" (SURVEY.md §4). The bootstrap recipe lives in
thinvids_tpu.core.devices (shared with the driver's dryrun entry point).
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from thinvids_tpu.core.devices import force_cpu_devices  # noqa: E402

force_cpu_devices(8)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running (sanitizer fuzz, big corpora); excluded "
        "from the tier-1 run (-m 'not slow')")


@pytest.fixture(scope="session")
def analysis_ctx():
    """(manifest, SourceTree over the package) — the same
    tree `cli.py check` analyzes. Shared by the subsystem-contract
    tests that migrated off the old grep guards (test_abr, test_live,
    test_compact, test_streaming); session scope so the ~70 modules
    are discovered and AST-parsed once per run, not once per file."""
    import thinvids_tpu
    from thinvids_tpu.analysis import SourceTree, default_manifest

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    tree = SourceTree(os.path.join(repo, "thinvids_tpu"))
    return default_manifest(), tree


@pytest.fixture(scope="module", autouse=True)
def _drop_compiled_programs():
    """Free each test module's compiled programs when it ends. One
    long tier-1 process otherwise keeps every program of ~700 tests
    alive, and XLA's CPU compiler then segfaults mid-run (seen in
    tests/test_rdo.py under jax 0.9.0; ROADMAP D0)."""
    yield
    import gc

    import jax

    jax.clear_caches()
    gc.collect()
