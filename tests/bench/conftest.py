"""Fixtures of the benchmark's CPU tests."""
import pytest

from benchtools import make_root


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return make_root(str(tmp_path_factory.mktemp("bench")))
