"""The package's lazy export table: every name it promises resolves, and a
name it does not promise is an AttributeError, not a late ImportError."""

import importlib

import pytest

import torchft_tpu


@pytest.mark.parametrize("name", sorted(torchft_tpu._EXPORTS))
def test_every_exported_name_resolves_to_its_modules_own(name):
    module = importlib.import_module(torchft_tpu._EXPORTS[name])
    assert getattr(torchft_tpu, name) is getattr(module, name)
    assert name in torchft_tpu.__all__ and name in dir(torchft_tpu)


@pytest.mark.parametrize("name", ["ProcessGroupBabyHost", "NoSuchName"])
def test_a_name_outside_the_table_is_an_attribute_error(name):
    assert name not in torchft_tpu.__all__
    with pytest.raises(AttributeError, match=name):
        getattr(torchft_tpu, name)
