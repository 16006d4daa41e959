import pytest

from lsmbench.tests.tiny import Tiny


@pytest.fixture(scope="session")
def tiny(tmp_path_factory):
    return Tiny(tmp_path_factory.mktemp("tiny"))
