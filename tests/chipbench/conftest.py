import pytest

import cbtiny


@pytest.fixture(scope="module")
def tiny_bench(tmp_path_factory):
    return cbtiny.make(tmp_path_factory.mktemp("chipbench"))
