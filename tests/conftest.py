"""Shared test config: CI shard markers.

Tier-1 runs as three parallel CI shards selected by pytest markers (see
.github/workflows/ci.yml).  Markers are assigned here from the test
module name so individual test files stay marker-free; any module neither
set claims falls into the "models" shard, whose CI expression is
``not kernels and not simwire`` — so the three shards always partition
the full suite and a new test file can never silently drop out of CI.
"""
from __future__ import annotations

import pytest

KERNEL_MODULES = {
    "test_kernels",
    "test_compress_pipeline",
    "test_erasure_kernel",
    "test_attention_backends",
    "test_ssm_oracles",
    "test_tpu_compile",
}
SIMWIRE_MODULES = {
    "test_sim_contacts",
    "test_sim_engine",
    "test_fastpath_equivalence",
    "test_constellation",
    "test_wire_codecs",
    "test_bench_harness",
    "test_channel",
    "test_obs",
    "test_obs_ledger",
    "test_obs_prof",
    "test_topology",
    "test_api",
    "test_faults",
}


def pytest_collection_modifyitems(items):
    for item in items:
        mod = item.module.__name__.rsplit(".", 1)[-1]
        if mod in KERNEL_MODULES:
            item.add_marker(pytest.mark.kernels)
        elif mod in SIMWIRE_MODULES:
            item.add_marker(pytest.mark.simwire)
        else:
            item.add_marker(pytest.mark.models)
