"""GPT-2's, DeepSeek-V2's and dots3-note-prev's serving programs are the
parent's: the SHA-256 of their lowered text at toy widths
(``tools/loweredsha.py``, a process of its own so that no other test's
blocks move a name) against the values recorded from the tree before PR 32
(commit eea6ea6) and, for dots3-note-prev's decode step and one prefill
program, from the tree before PR 35 (commit 28ca294); Olmo-Hybrid's decode
step and one prefill program (slot state in the carry) from PR 40's own
tree, the first that has them. A PR that does not
mean to touch those models' programs keeps them; one that does records anew
(``JAX_PLATFORMS=cpu python tools/loweredsha.py``) and says so."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RECORDED = {
    "gpt2.decode": "76b55c8cc854ebb98588fe1698e09959ca505b9e0b0b1fa67b3bea44f7ff3195",
    "gpt2.prefill8": "5331b6a2166d716c0af61623ada3f9c264c314b117f836fcdc5f50868c9328e6",
    "gpt2.prefill16": "11b989eaafe81e7e9d28026261f64714c030e15bc975740b9b85e49cd5a2243b",
    "deepseek_v2.decode": "d8882c71e5cfe3c884f3f9e60c130aeb077ad90820ddd9ade253c9e11e5ad443",
    "deepseek_v2.prefill8": "e93b9e0451fabb9396df72339c1ae2ca81b20560056374a063b162322f259d80",
    "deepseek_v2.prefill16": "8bb85cb5486e5fb526cbfe108a276796656747c720cfff7092c32df9bb461366",
    "deepseek_v2.prefill32": "312dbc5daaa8dc81db10a4862906aa632c17fd1e4579000008c6ae0dd7b9f86a",
    "deepseek_v2.prefill64": "53764d7519643636edc56b11c028c5c3ed80e3b8f9d149ae2995dae5c52deee9",
    "dots3_note.decode": "adf8fc0ee4eb9d46e7bbd4d6fdc842dfecd02404eb5d7b262ae6fce55cfdfab6",
    "dots3_note.prefill16": "5ad8ede32b118409f13d5179c7e3ef89744aa992c977fea0822f24d15a709a40",
    "olmo_hybrid.decode": "97083b6f120153213f4f5ec83166d2b72e761d52db92f572cf02f49156c22e23",
    "olmo_hybrid.prefill16": "3cb9459a0d0052474c4bbb03ac8c54d5c71d087cb4b560653cf1725cc16e526a",
}


@pytest.fixture(scope="module")
def lowered():
    run = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "loweredsha.py")],
        capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert run.returncode == 0, run.stderr[-2000:]
    return json.loads(run.stdout[run.stdout.index("{"):])


@pytest.mark.parametrize("program", sorted(RECORDED))
def test_a_program_of_another_model_keeps_the_parents_lowered_text(lowered,
                                                                   program):
    assert set(lowered) == set(RECORDED)
    assert lowered[program] == RECORDED[program]
