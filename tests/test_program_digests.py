"""The configurations that share code with a new one keep the programs
they had: the traced text (jaxpr) of the two paged serving programs of the
sigmoid-routed configurations and the one-row latent ones, at their test
sizes, is the text of the commit before LongCat-Flash-Chat was added
(``route`` gained a scoring function and a renormalisation switch,
``_project`` two scales, ``_ffn`` a third kind of layer, the latent pool a
count of sublayers: each is data that these configurations leave at the
value that traces no operation). A change that is MEANT to alter one of
these programs re-records its digest here and says so."""

import functools

import jax
import pytest

import program_text as PT

# sha256[:16] of str(jax.make_jaxpr(program)) at the utils' tiny configs.
# The decode programs' are those recorded at the parent of PR 46 and equal
# on its final tree. The prefill programs' were re-recorded by PR 48, which
# MEANT to alter all five: a chunk's rows go into the pool as the whole
# windows they touch (paged_cache.write_chunk), not as one scattered row a
# token; the pool they leave is the same (tests/test_write_chunk.py).
# ``exaone_moe``'s prefill program was re-recorded by PR 51, which MEANT to
# alter it (and ``smallthinker``'s, the same dialect, whose pair joins
# here): a chunk attends the whole tiles it can see of its slot's row and
# of the ring (hybrid.block_prefill through engine._attend_occupied) and
# the ring's gather lies inside the window branch; the logits are the
# whole-row form's (tests/test_exaone_moe.py,
# tests/test_smallthinker_serving.py). Its
# decode program and both programs of the other four are the parent's.
# PR 54 re-recorded ``kimi_linear``'s prefill program (below) and added
# ``gpt``; the others are its parent's
PARENT = {
    "dots_vlm": ("1f4bc36bb35dad08", "6e38d962ac6c627a"),
    "exaone_moe": ("5156e3f3e5fb0868", "4a6802b404de5550"),
    "smallthinker": ("bfccd691bf9b57c4", "98f190518fa6bd18"),
    # prefill re-recorded by PR 54, which MEANT to alter it: the chunk
    # form's unit-triangular solve is matrix products (kda._solve_unit_lower)
    # in place of lax.linalg.triangular_solve; the numbers are the
    # recurrence's (tests/test_kimi_linear.py, tests/test_qwen3_next.py)
    "kimi_linear": ("1743aa2d78785415", "a284d9953d44de1f"),
    "zaya": ("71c82477980eded6", "008d914cbe2106ca"),
    "jamba": ("7bf3f1d239e4fc52", "3e097490323d7ce2"),
    # the plain K and V pools (GPT-2 XL's programs), recorded at the parent
    # of PR 54, whose `_attn_*_paged` gained an output gate, q/k norms and
    # rotate-half rotary as data that this configuration leaves absent
    "gpt": ("7b43959e8c186def", "6a0a65698605df44"),
}


@pytest.mark.parametrize("name", sorted(PARENT))
def test_serving_programs_are_the_parents(name):
    U = __import__(name + "_util")
    cfg = U.tiny_config()
    # shapes only: no weight is made
    params = jax.eval_shape(functools.partial(U.tiny_params, cfg))
    text = PT.serving_programs_text(cfg, params)
    assert (PT.digest(text["prefill_slot"]),
            PT.digest(text["decode_slots"])) == PARENT[name]
