"""The names the models' blocks carry in a device trace.

``with scopes.block("attn.core"):`` around the code that traces a block puts
``blk.attn.core`` into the ``op_name`` of every HLO instruction traced under
it (and of the fusion whose root such an instruction becomes), which the
profiler records as the op's framework name: chipbench/trace_scopes.py books
a step's device time by it. A scope is metadata and nothing else: the
compiled program is the same instruction for instruction with or without
(tests/test_block_scopes.py), so the scopes are always there and nothing
turns them off. The innermost ``blk.`` scope names an op; what no scope
covers (a scan's slice of a stacked weight, carried copies) reads as
``unscoped``, on purpose.

The names are dotted so that a reader can take a prefix (``ffn.experts``
covers ``ffn.experts.glue``)."""

from __future__ import annotations

import jax

BLOCKS = (
    "embed",            # the token (and image) embedding gather
    "attn.proj",        # norm, q/k/v and latent projections, rotary, gates, wo
    "attn.index",       # a selecting block's indexer: projection, scores, top-k
    "attn.expand",      # a window's latent rows carried out to keys and values
    "attn.core",        # the attention itself, kernel or XLA
    "kv.write",         # rows written into a page, window, index or state pool
    "ffn.router",       # router scores, top-k, the on-device pair counts
    "ffn.experts",      # the routed experts in every form
    "ffn.experts.glue",  # the grouped form's layout and its two gathers
    "ffn.shared",       # shared experts
    "ffn.dense",        # a dense layer's FFN
    "state.proj",       # a state-space layer's projections, conv, gated norm
    "state.update",     # its recurrence, step or chunked scan
    "head",             # final norm and lm_head
    "sample",           # the sampler
)

PREFIX = "blk."


def block(name: str):
    """The scope of block ``name``, one of :data:`BLOCKS`
    (tests/test_block_scopes.py holds the programs to that vocabulary)."""
    return jax.named_scope(PREFIX + name)
