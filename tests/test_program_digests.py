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
# PR 54 re-recorded ``kimi_linear``'s prefill program and added ``gpt``
# (its chunk form's unit-triangular solve is matrix products,
# kda._solve_unit_lower, in place of lax.linalg.triangular_solve; the
# numbers are the recurrence's: tests/test_kimi_linear.py,
# tests/test_qwen3_next.py).
# PR 57 re-recorded BOTH programs of the five sparse configurations, and
# MEANT to alter all ten: the expert layer's grouped product takes the
# layer's own ``count`` group sizes (expert_share._grouped: off a TPU
# ``ragged_dot`` over the layer's slice of the stacked experts, where it
# ran over every sparse layer's groups with the layer's sizes written into
# a zero vector), its sizes are a compare and a sum where they were a
# scatter-add, and the un-sort is a second sort and one gather in [K, T, d]
# order where it was a scattered inverse and [T, K, d]; the layer's output
# and counters are the parent's (tests/test_grouped_matmul.py). ``jamba`` (no experts) and ``gpt`` hold
# the parent's text
PARENT = {
    "dots_vlm": ("2b780e38a2e951ae", "e67ccb088e089a34"),
    "exaone_moe": ("e0d4515839ff1e9d", "aa76f196d5f60505"),
    "smallthinker": ("57547f5c185d7057", "a67e09874ab75224"),
    "kimi_linear": ("b12ec40e5c564edd", "06428131a43a079b"),
    "zaya": ("5aedcea79e1e57cd", "6ecc263b8d76ed88"),
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
