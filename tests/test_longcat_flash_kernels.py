"""The longcat_flash dialect with ``decode_impl`` "pallas" off a TPU: every
Mosaic kernel of the two serving programs interpreted, once, at the
smallest shape that has two blocks. The dialect itself:
tests/test_longcat_flash.py; the pool's layout in the programs compiled for
a v5e: tests/test_longcat_flash_aot.py."""


import numpy as np

import longcat_flash_util as U

SOUND = 2e-4


def test_the_kernels_serve_what_the_portable_path_serves(
        pallas_interpret, monkeypatch):
    """``decode_impl`` "pallas" off a TPU, every Mosaic kernel interpreted
    (``mla_prefill``, ``mla_decode``, the grouped products), at the
    smallest shape that has two blocks: a prompt of 6 in blocks of 4."""
    cfg = U.tiny_config()
    params = U.tiny_params(cfg)
    prompts = [np.random.default_rng(4).integers(1, 96, 6)]
    monkeypatch.setenv("DS_PAGED_DECODE_IMPL", "pallas")
    srv, got = U.serve_logits(cfg, params, prompts, 3, num_slots=1,
                              prefill_chunk=8)
    assert srv.engine.decode_impl == "pallas"
    toks, lg = got[0]
    want, _ = U.reference().logits(params, toks[:-1], U.hp_of(cfg))
    assert float(np.abs(lg - np.asarray(want)[5:]).max()) < SOUND
    assert srv.stats["mla_prefill_tiles_kernel_total"] == 6
