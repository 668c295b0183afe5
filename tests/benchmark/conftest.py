"""Fixtures of the benchmark's CPU tests (the helpers are in bench_helpers)."""

import json

import pytest

from bench_helpers import ROOT, load_harness


@pytest.fixture(scope="session")
def harness():
    return load_harness()


@pytest.fixture(scope="session")
def manifest():
    return json.loads((ROOT / "BENCHMARK.json").read_text())
