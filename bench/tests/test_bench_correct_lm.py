"""`cases.py` for the four-chip LM cell, on one device (`tiny.one_device`)."""
import pytest

from bench.tests.cases import *  # noqa: F401,F403


@pytest.fixture
def cell():
    return "qwen2-0.5b.ici4"
