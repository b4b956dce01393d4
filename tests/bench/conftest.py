"""The benchmark's tests: `bench` is imported from the checkout's root."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    from benchroot import make_root
    return make_root(tmp_path_factory.mktemp("bench_root"))
