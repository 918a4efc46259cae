"""What the layer loop of inference/linear.py asks of a model whose layers
are of TWO kinds of cache state: some keep a per-slot RECURRENT state
(linear attention: models/kimi_linear.py; a state-space mixer:
models/jamba.py), the others page their history behind the slot's block
table. The config says which is which (``attn_kinds``: per layer, 0
recurrent, 1 paged) and how many leading layers have a dense FFN of their
own stack (``n_dense_layers``); nothing here knows the rule that writes the
state."""

import jax.numpy as jnp
import numpy as np


def layer_bases(cfg, n_blocks: int, n_slots: int):
    """Per layer, by layer index: its index ``attn`` in its kind's
    parameter stack, where its rows start in the flat paged pool
    (``rows``; ``n_blocks`` blocks a paged layer) and its slots in the
    flat recurrent state and convolution tails (``state``; ``n_slots`` a
    recurrent layer), each by the kind's OWN layer counter (the other
    kind's entry is 0 and unread), and the layer's ``index`` in the stack
    of FFNs behind the leading dense ones (a sparse layer's row in the
    dispatch's routing record)."""
    kinds = cfg.attn_kinds
    # a layer's index among the layers of its own kind
    own = np.where(kinds == 1, np.cumsum(kinds == 1) - 1,
                   np.cumsum(kinds == 0) - 1).astype(np.int32)
    layers = np.arange(cfg.n_layers)
    bases = {"attn": own,
             "rows": np.where(kinds == 1, own * n_blocks, 0),
             "state": np.where(kinds == 0, own * n_slots, 0),
             "index": np.maximum(layers - cfg.n_dense_layers, 0)}
    return {k: jnp.asarray(v.astype(np.int32)) for k, v in bases.items()}


def layer_runs(cfg):
    """How the kinds cut the layers behind the leading dense ones
    (inference/linear.py ``run_layers``): (``starts``, ``counts``,
    ``behind``). Run ``r`` is the ``counts[r]`` recurrent layers from layer
    ``starts[r]`` on and the paged layer that ends them; ``behind`` =
    (first layer, count) of the recurrent layers behind the last paged one.
    Layer indices from 0."""
    starts, counts = [], []
    at = cfg.n_dense_layers
    for l in range(at, cfg.n_layers):
        if cfg.attn_kinds[l] == 1:
            starts.append(at)
            counts.append(l - at)
            at = l + 1
    return (np.asarray(starts, np.int32), np.asarray(counts, np.int32),
            (at, cfg.n_layers - at))
