"""What every configuration file is held to, whatever its architecture;
shared by ``test_chipbench.py`` (the real files) and
``test_architectures.py`` (the stub). Not a test module: a test module is
not imported from another."""

import re

from chipbench import architectures

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def check_config_file(data):
    """The architecture's module says which keys are widths, which equal
    the published ones and which a file must have."""
    arch = architectures.of(data)
    assert len(data["source"]) <= 200
    assert NAME.match(data.get("architecture", architectures.DEFAULT))
    assert set(arch.REQUIRED) <= set(data), set(arch.REQUIRED) - set(data)
    assert set(arch.WIDTHS) <= set(arch.REQUIRED)
    assert not set(data["reduced"]) & set(arch.WIDTHS)  # no width is cut
    for k in arch.AS_PUBLISHED:
        assert data[k] == data["published"][k], k
    for k, v in data["published"].items():
        if k in data and data[k] != v:
            assert k in data["reduced"], k
    assert arch.program_config(data) is not None
    assert arch.cache_token_bytes(data) > 0
    assert arch.n_params(data) > sum(arch.matmul_params(data)) > 0
    assert callable(arch.reference().init_params)
    assert callable(arch.reference().logits)
