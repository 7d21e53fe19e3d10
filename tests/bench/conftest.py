"""The CPU-sized benchmark tree the whole-run tests share."""

import os

import pytest

from tests.bench.benchroot import BENCH, make_root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("benchroot"), os.path.join(os.path.dirname(BENCH), "BENCHMARK.json"))
