"""Layer groups in the KV pool, the general top-k and the per-layer window and
rotary table (PR 30) ADAPT to the model: a model whose layers all see alike is
one group with one block table, and Mixtral routes top-2 renormalised. Their
traced serving programs are the ones the commit before traced."""

import hashlib

import jax
import pytest

from tests.unit.inference.v2.program_hashes import traced_program_texts

# sha256 of str(jax.make_jaxpr(...)) (addresses blanked) of the programs
# ``program_hashes.traced_program_texts`` names, taken at the commit BEFORE the
# groups (e47351e, jax 0.9.0), under tests/conftest.py's eight virtual CPU devices.
# The two ``kernel.forward.128x8x8`` programs (the query-tiled grid) were
# re-recorded in PR 42: the one difference is the kernel's body, where a pass that
# owns one token of its tile computes that token alone, and again in PR 51, whose
# kernel's one-token arm became the arm of every pass of no more rows than one block
# (``block`` is 0 here: the same passes take it, their arithmetic is what it was;
# the selection's and the placing's index arithmetic are written for a block; every
# pass starts its walk's first chunk under its insert, the same copies earlier); the
# per-token grid's, the chunk's and the gather arm's ten are the parent's still. The four ``decode_loop``
# programs were re-recorded in PR 46, which took the loop's unused temperature
# and key away: two input variables and one constant of the scan (the key, which
# JAX had moved out of the carry it was never changed in) fewer, the equations
# the same, one for one (CHANGES.md, PR 46).
_PARENT = {
    "mixtral.gather.forward.8x8x4": "86f8f4891c9c332a20f1ab9c7b3102c75d6abc68b5de33296a2c91e4354762fe",
    "mixtral.gather.forward.128x8x8": "cd3542097441a1448f98af3dfc5355e9e87e4cad4a485bf9a4955d6651b04514",
    "mixtral.gather.decode_loop": "3b53573caf8b45176d5c1df4077f2e8c9ad48a9c93a71e53a1b9f66903f06011",
    "mixtral.kernel.forward.8x8x4": "9c9226f37fb527248717741de88c14b927be58fec10ed5a431c07501ec327cfb",
    "mixtral.kernel.forward.128x8x8": "b25ee175221ecc799cf90c2be47e36e1a79b492f4c1fc1d927bb09dc941f2b74",
    "mixtral.kernel.decode_loop": "13fc8526920cc178cc3036371c2acd7bc2f6b9d4f033a627f72dd92bce3037c0",
    "mistral.gather.forward.8x8x4": "751033dec5183c6a1e986d04784c353762cbd51dd4d420ba32ee2857ecb4e0d1",
    "mistral.gather.forward.128x8x8": "c84cdeeec59041dd601cf9c67a5908720e358b67563d25bcea0e1ddff1b05d9b",
    "mistral.gather.decode_loop": "fb03027c1dc3f3f5a560949add887b9bda804516d6851336308a5d110725c81a",
    "mistral.kernel.forward.8x8x4": "7863a4ef1c64d31ba1fe38293e800230cf9d248ba3fc33430b9d5c787374b774",
    "mistral.kernel.forward.128x8x8": "26bbd817ae11653e2f43a3a21bdbfeec30ee2847f4e625983750d54f6055df8b",
    "mistral.kernel.decode_loop": "8f0a59ff5d887619ae5a0c9ea912d5e35c38714807a0567cb6163780de18a707",
}


@pytest.fixture(scope="module")
def texts():
    if jax.__version__ != "0.9.0":
        pytest.skip("the recorded jaxpr text is jax 0.9.0's")
    return traced_program_texts()


@pytest.mark.parametrize("name", list(_PARENT))
def test_a_one_group_model_traces_the_program_it_always_did(texts, name):
    assert hashlib.sha256(texts[name].encode()).hexdigest() == _PARENT[name]
