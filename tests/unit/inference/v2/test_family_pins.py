"""Every served family's tiny engine traces the programs, carries the scopes
and counts the span args it did on PR 59's parent (``family_pins.py``: what is
read and how the table was recorded). A refactor of
``model_implementations/`` that means to change no behaviour leaves every case
here as it is; a PR that changes a family's program on purpose re-records that
family's entry and says so."""

import jax
import pytest

from tests.unit.inference.v2 import family_pins

_TABLE = family_pins.recorded()
_CASES = [(family, what) for family, pins in _TABLE["families"].items() for what in pins]


@pytest.mark.parametrize("family,what", _CASES, ids=[f"{f}-{w}" for f, w in _CASES])
def test_a_family_traces_carries_and_counts_what_it_did(family, what):
    if what in ("put", "chunk") and jax.__version__ != _TABLE["jax"]:
        pytest.skip(f"the recorded jaxpr text is jax {_TABLE['jax']}'s")
    assert family_pins.observed(family)[what] == _TABLE["families"][family][what]


def test_every_served_family_is_in_the_table():
    assert set(_TABLE["families"]) == set(family_pins.FAMILIES)
