"""The paged KV cache: the one owner of the page pool's layout.

A pool is the stacked pair ``k_pages`` / ``v_pages``, each
``[n_layers, n_blocks, block, n_kv_heads, head_dim]``. That order of axes is
relied on here and in the two attention ops this package calls
(``ops/attention.py``, ``ops/pallas_paged_attention.py``), and nowhere else:

- ``pages``: the geometry (``PageGeometry``: the pools' shapes, and what
  rides with them for an engine: a state pool, a step's counts; what of it
  serves on one unsharded chip alone, and ``/health``'s words for all of
  it), allocation and the sharding rule, the two writes (a token a lane, a
  run of tokens a sequence), the two reads (decode attention and which op
  runs it, the prefix gather), and the block-wise export and import on the
  device;
- ``state``: the pool beside the pages that a state-space layer's recurrent
  state lives in, indexed by engine slot, and the one value (``state.Cache``)
  that carries pages and state through a step;
- ``wire``: what a handoff to another engine looks like in bytes and
  headers, and the checks on what arrives.

Imports go one way: ``ops`` <- ``kvcache`` <- ``models``, ``parallel`` <-
``engine``. Nothing here imports ``models``, ``parallel`` or ``engine``;
which block ids are free or cached is ``engine/blocks.py``'s business, which
knows a count and a page size and no layout.
"""
