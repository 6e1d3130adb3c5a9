"""The models' serving programs are the recorded ones: the SHA-256 of their
lowered text at toy widths (``tools/loweredsha.py``, a process of its own so
that no other test's blocks move a name) against recorded values: GPT-2's
from the tree before PR 32 (commit eea6ea6); Olmo-Hybrid's decode step and
one prefill program (slot state in the carry) from PR 40's own tree, the
first that has them; SmallThinker's decode step and one prefill program from
the tree before PR 41 (commit a35b2af: every expert held, so PR 41's branch
over the sorted pairs is not built and the programs are that tree's);
DeepSeek-V2's and dots3-note-prev's from PR 41's own tree, whose expert
layers return one more count (``moe_whole_path``); MiniCPM-SALA's decode step
(a read over a table of selected pages, a pool of
compressed keys, a second kind of slot state) from PR 43's own tree, the
first that has them; its one prefill program (one stretch: no predicate)
from PR 46's tree, which brings one more count back behind the first token
(``positions_run``). A PR that does not mean
to touch those models' programs keeps them; one that does records anew
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
    "deepseek_v2.decode": "728b271c1aba5f19e9593e1fdbd24056952585ed0ccaa7297efa049d95eafad1",
    "deepseek_v2.prefill8": "665fc92b928209b915b580c6dde780f672cb25181552dab7678869057b3bf4bd",
    "deepseek_v2.prefill16": "cbe10f9db2a3298f3ad4df411cf989772c62af26ff9f8a39e95212b5558eb47d",
    "deepseek_v2.prefill32": "c87fb040ecf94c42af31ca0098d70ed207fdcaa3322ad6fa31b9dfc980605791",
    "deepseek_v2.prefill64": "4c1ceb791c7bf7db10dfaa8a5bd270cc5063e05dad2f7d00d536aea8ce079bf0",
    "dots3_note.decode": "984d96d7a2a7633d84247133ed4628af1a275b2b55a9a3e6d568e63c4a7b6382",
    "dots3_note.prefill16": "f6eea0024e502bf151dee4204bc572d50a129dba3d455c5db50e65209b146458",
    "olmo_hybrid.decode": "97083b6f120153213f4f5ec83166d2b72e761d52db92f572cf02f49156c22e23",
    "olmo_hybrid.prefill16": "3cb9459a0d0052474c4bbb03ac8c54d5c71d087cb4b560653cf1725cc16e526a",
    "smallthinker.decode": "05fe721bb8ff83fee9e490b4919ccd80dd3792736efc587c68e2486c1aae8e88",
    "smallthinker.prefill16": "513b547450397901779089169e03127b038c1e1f6fe760843328cb103dd25fc5",
    "minicpm_sala.decode": "f1ade0fc065414fd12957de6fe4879444d3f3d118baef4cd61ef1f49858f2a57",
    "minicpm_sala.prefill64": "9816a9e81c000be7a15112cc6c689f45fba6005d5eaa6743cd43563272d90012",
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
