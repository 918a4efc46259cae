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

# sha256[:16] of str(jax.make_jaxpr(program)) at the utils' tiny configs,
# recorded at the parent of PR 46 and equal on its final tree
PARENT = {
    "dots_vlm": ("95ad7f2fb99d5cd2", "6e38d962ac6c627a"),
    "exaone_moe": ("07f8e1ca05c9bbf4", "4a6802b404de5550"),
    "kimi_linear": ("dfd27d16662d0b57", "a284d9953d44de1f"),
    "zaya": ("13bcd8bc05ad8588", "008d914cbe2106ca"),
    "jamba": ("ef1032d1179aa9d3", "3e097490323d7ce2"),
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
