"""Pipeline-parallel SERVING: paged decode + prefill over a ``pp``(×``tp``) mesh.

Models too deep for one chip/slice even under TP serve through a stage ring
(the reference delegates intra-engine parallelism to vLLM — SURVEY §2.12;
this is the TPU-native engine-half equivalent, composing with the GPipe
training pipeline in parallel/pipeline.py):

- The stacked layer axis of params AND paged KV buffers shards over ``pp``:
  stage s owns layers [s·L/P, (s+1)·L/P) and exactly those layers' pages.
- One decode step = P ring turns inside a ``lax.fori_loop``. Every stage
  applies its layer slab each turn (SPMD), but only the stage whose turn it
  is holds real activations; off-turn KV writes are redirected to the trash
  block 0 (cheap index select — no page-buffer masking). A single
  ``ppermute`` moves activations to the next stage; the ring wrap returns
  the final hidden state to stage 0, a psum-select replicates it, and the
  (replicated) head + sampler run everywhere so the sampled token is
  identical on all stages — decode stays closed under the ring.
- Latency per token is inherently stage-serial (P slab times + P hops);
  throughput comes from the decode batch riding each turn. Prefill uses the
  same ring at [1, S] shapes with per-slab KV scatters.

**EP composition** (``ep > 1``): the mesh carries a third ``ep`` axis and
MoE expert weights shard over it (w1/w3/w2's experts dim). Each stage slab
ranks ALL experts with the replicated router, computes its local E/ep
experts' outputs, and the gated combine psums over ``("tp", "ep")`` —
the deep-MoE deployment shape (layers over pp, experts over ep, FFN hidden
over tp). With ``ep == 1`` the experts are whole on every device and the
same code path degenerates to dense-over-experts.

**TP composition** (``tp > 1``): the mesh is ``(pp, tp, ep)``. Within each
stage's slab the layer math is Megatron-TP — column-parallel wq/wk/wv/w1/w3,
row-parallel wo/w2 (shardings.param_pspecs), one ``psum`` over ``tp`` after
the attention output projection and one after the FFN, riding ICI inside the
stage while ``ppermute`` hops between stages. KV pages shard over BOTH axes:
layers on ``pp``, kv-heads on ``tp`` (the paged gather/scatter stays
collective-free — GQA group mapping is shard-local because tp divides
n_kv_heads). Embedding shards the model dim and lm_head the vocab dim over
``tp``; both are re-assembled with a psum-scatter (invariant output, so the
sampled token is bit-identical on every device).

Engine integration (engine/core.py): with ``pp_size > 1`` the engine swaps
its decode-chunk / prefill jits for these — same signatures, so the
device-op layer (multihost replay included) is unchanged.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..engine.sampling import sample_tokens
from ..kvcache import pages
from ..models import llama
from ..models.configs import ModelConfig
from ..ops import rms_norm, rope_table
from .serve import validate_tp
from .shardings import param_pspecs

__all__ = ["make_pp_mesh", "shard_params_pp",
           "make_pp_decode_chunk", "make_pp_prefill",
           "make_pp_prefill_with_prefix"]

PP_SERVE_AXES = ("pp", "tp", "ep")


def make_pp_mesh(devices=None, pp: int | None = None, tp: int = 1,
                 ep: int = 1) -> Mesh:
    """(pp, tp, ep) serving mesh. tp=1/ep=1 keep the pure stage ring (the
    extra axes are size 1 and their collectives are XLA-elided identities).
    ``ep > 1`` shards MoE experts within each stage's slab — the deep-MoE
    deployment shape (stage ring over pp, experts split over ep, FFN hidden
    over tp)."""
    devices = list(devices if devices is not None else jax.devices())
    pp = pp or (len(devices) // (tp * ep))
    if pp * tp * ep > len(devices):
        raise ValueError(f"pp*tp*ep={pp}*{tp}*{ep} exceeds "
                         f"{len(devices)} devices")
    arr = np.array(devices[: pp * tp * ep]).reshape(pp, tp, ep)
    return Mesh(arr, PP_SERVE_AXES)


def _param_specs(cfg: ModelConfig):
    """Stage split on the stacked-L axis composed with Megatron TP specs.

    The per-layer TP/EP dims come from shardings.param_pspecs with the
    leading (unsharded) L entry replaced by "pp": MoE expert axes keep
    their ``ep`` placement (each stage slab computes its local experts and
    the combine psums over ``("tp", "ep")``), the FFN hidden dim shards on
    tp. Embedding shards the model dim, lm_head the vocab dim (re-assembled
    with _tp_full in the bodies).
    """
    tp_layers = param_pspecs(cfg)["layers"]

    def stage(spec: P) -> P:
        return P("pp", *spec[1:])

    return {"embed": P(None, "tp"),
            "layers": {k: stage(v) for k, v in tp_layers.items()},
            "final_norm": P(), "lm_head": P(None, "tp")}


def _ffn_psum(cfg: ModelConfig, lp, h):
    """FFN partial + its reduction, shard_map-local. Dense: llama._ffn then
    psum over tp. MoE: the expert axes live on ``ep`` (possibly size 1 —
    the specs place them there unconditionally, so the params are typed
    ep-varying and the reduction MUST cover ep to keep the carry invariant).
    The router is replicated so every device ranks ALL experts; the expert
    einsums see only the local E/ep slice — slice the matching gate block
    by ep rank, combine locally, and psum over ("tp", "ep")."""
    if "router" not in lp:
        return jax.lax.psum(llama._ffn(cfg, lp, h), "tp")
    squeeze = h.ndim == 2  # decode step: [B, D]
    if squeeze:
        h = h[:, None]
    logits = (h @ lp["router"]).astype(jnp.float32)          # [B, S, E] full
    top_vals, top_idx = jax.lax.top_k(logits, cfg.experts_per_token)
    gates = jax.nn.softmax(top_vals, axis=-1)
    onehot = jax.nn.one_hot(top_idx, cfg.n_experts, dtype=h.dtype)
    weights = jnp.einsum("bske,bsk->bse", onehot, gates.astype(h.dtype))
    e_loc = lp["w1"].shape[0]                                # E/ep (static)
    lo = jax.lax.axis_index("ep") * e_loc
    w_loc = jax.lax.dynamic_slice_in_dim(weights, lo, e_loc, axis=2)
    up = jnp.einsum("bsd,edf->bsef", h, lp["w1"])
    gate = jnp.einsum("bsd,edf->bsef", h, lp["w3"])
    out = jnp.einsum("bsef,efd->bsed", jax.nn.silu(up) * gate, lp["w2"])
    y = jnp.einsum("bsed,bse->bsd", out, w_loc)
    y = jax.lax.psum(y, ("tp", "ep"))
    return y[:, 0] if squeeze else y


def _tp_full(x, n_tp: int, axis: int):
    """Re-assemble a tp-sharded axis into the full (replicated, invariant)
    array: scatter the local shard at its offset and psum over tp. Identity
    when tp == 1 (psum over a size-1 axis), but always emitted so the value's
    varying-axes type drops ``tp`` and sampling stays replicated.

    A tiled ``all_gather`` would move half the bytes, but its output stays
    *varying* over tp in shard_map's replication typing (no invariant
    all_gather / pcast-to-invariant exists in this JAX), which would poison
    every downstream out_spec; the psum form is typed invariant. The arrays
    here ([B, D] embeds / [B, V] logits) are activation-sized — the extra
    half-pass is noise next to the per-turn weight traffic."""
    size = x.shape[axis]
    i = jax.lax.axis_index("tp")
    shape = x.shape[:axis] + (size * n_tp,) + x.shape[axis + 1:]
    full = jax.lax.dynamic_update_slice_in_dim(
        jnp.zeros(shape, x.dtype), x, i * size, axis)
    return jax.lax.psum(full, "tp")


def _decode_slab(cfg: ModelConfig, params, x, k_pages, v_pages, tables,
                 positions, eff_blk, slot):
    """One stage's layer slab for one decode token (shard_map-local view:
    L/P layers, Hkv/tp kv-heads, E/ep experts) with Megatron-TP collectives:
    psum over tp after the attention output projection, over (tp, ep) after
    the FFN. KV for the new token scatters into (``eff_blk``, ``slot``) (the
    caller trash-redirects off-turn writes). Shared by the broadcast ring and
    the lane-group interleave."""
    B = x.shape[0]
    Dh = cfg.head_dim
    cos, sin = rope_table(positions, Dh, cfg.rope_theta)
    seq_lens = positions + 1

    def body(x, layer_in):
        lp, layer = layer_in                                # local layer index
        h = rms_norm(x, lp["ln_attn"], cfg.norm_eps)
        q = (h @ lp["wq"]).reshape(B, -1, Dh)               # local heads
        k = (h @ lp["wk"]).reshape(B, -1, Dh)
        v = (h @ lp["wv"]).reshape(B, -1, Dh)
        q, k = llama.qk_normed(cfg, lp, q, k)
        q = llama.apply_rope(q[:, None], cos[:, None], sin[:, None])[:, 0]
        k = llama.apply_rope(k[:, None], cos[:, None], sin[:, None])[:, 0]
        attn = pages.decode_attention(q, k_pages, v_pages, layer, tables,
                                      seq_lens, k, v)
        x = x + jax.lax.psum(attn.reshape(B, -1) @ lp["wo"], "tp")
        h = rms_norm(x, lp["ln_mlp"], cfg.norm_eps)
        x = x + _ffn_psum(cfg, lp, h)
        return x, (k, v)

    x, (k_cur, v_cur) = jax.lax.scan(
        body, x, (params["layers"], pages.layer_indices(k_pages)))
    k_pages, v_pages = pages.write(k_pages, v_pages, k_cur, v_cur, eff_blk,
                                   slot)
    return x, k_pages, v_pages


def _ring_decode_step(cfg: ModelConfig, n_stages: int, n_tp: int, perm,
                      stage, params, tokens, positions, k_pages, v_pages,
                      block_tables):
    """One token for all lanes through the stage ring. Local (per-shard)
    views: params.layers / pages carry L/P layers and Hkv/tp kv-heads.
    Returns (logits replicated, pages)."""
    blk_idx, slot = pages.token_slots(k_pages, block_tables, positions)

    x0 = _tp_full(params["embed"][tokens], n_tp, axis=1)    # [B, D]
    zero = jnp.zeros_like(x0)

    def slab(x, k_pages, v_pages, active):
        """This stage's layers on x; KV writes trash-redirected off-turn."""
        eff_blk = jnp.where(active, blk_idx, pages.TRASH_BLOCK)
        return _decode_slab(cfg, params, x, k_pages, v_pages, block_tables,
                            positions, eff_blk, slot)

    def turn(t, carry):
        x, k_pages, v_pages = carry
        x = jnp.where(stage == 0, jnp.where(t == 0, x0, x), x)
        x, k_pages, v_pages = slab(x, k_pages, v_pages, active=stage == t)
        x = jax.lax.ppermute(x, "pp", perm)
        return x, k_pages, v_pages

    x = jax.lax.pcast(zero, 'pp', to='varying')
    x, k_pages, v_pages = jax.lax.fori_loop(
        0, n_stages, turn, (x, k_pages, v_pages))
    # Ring wrap parked the final activations back on stage 0; replicate.
    x = jax.lax.psum(jnp.where(stage == 0, x, jnp.zeros_like(x)), "pp")
    h = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _tp_full((h @ params["lm_head"]).astype(jnp.float32),
                      n_tp, axis=1)
    return logits, k_pages, v_pages


def _broadcast_chunk_body(cfg, n_stages, n_tp, perm, decode_chunk,
                          params, tokens, positions, k_pages, v_pages,
                          block_tables, key, temps, top_k, top_p):
    """K fused decode+sample broadcast-ring steps (all lanes every turn)."""
    stage = jax.lax.axis_index("pp")
    keys = jax.random.split(key, decode_chunk)

    def step(carry, k_step):
        tokens, positions, k_pages, v_pages = carry
        logits, k_pages, v_pages = _ring_decode_step(
            cfg, n_stages, n_tp, perm, stage, params, tokens, positions,
            k_pages, v_pages, block_tables)
        nxt = sample_tokens(logits, k_step, temps, top_k, top_p)
        return (nxt, positions + 1, k_pages, v_pages), nxt

    (_, _, k_pages, v_pages), toks = jax.lax.scan(
        step, (tokens, positions, k_pages, v_pages), keys)
    return toks, k_pages, v_pages


def make_pp_decode_chunk(cfg: ModelConfig, mesh: Mesh, decode_chunk: int,
                         interleave: bool | str = "auto"):
    """Drop-in for TpuEngine._decode_chunk_impl under pp(+tp): same
    signature, K fused decode+sample ring steps per dispatch.

    Two schedules, chosen per traced batch shape (the engine's decode batch
    bucketing retraces per PoW2 batch, so a single returned callable serves
    both): the **broadcast ring** runs every stage on ALL B lanes every turn
    with only one stage holding real activations — (P-1)/P of the slab
    compute and KV reads are garbage; the **lane-group interleave** splits
    the batch into P groups of B/P and keeps the pipeline full: at turn t
    stage s works group (t-s) mod P, so each stage touches B/P real lanes
    per turn and one group's token completes per turn in steady state.
    Group g's token j enters stage 0 at turn g+jP (the ring wrap carries its
    previous final hidden back to stage 0, where the head + sampler +
    embedding run — real only on stage 0, and the schedule-driven position
    bookkeeping is stage-invariant so every stage's copy agrees). A chunk of
    K tokens/lane takes K·P + P turns; the P-turn fill/drain is amortized
    over K·P. Trade-off: the lm_head weights are read every turn instead of
    every P turns — negligible for the deep models pp exists for (head ≪
    layer stack), and divided by tp. ``interleave="auto"`` picks the
    interleave whenever the traced batch splits evenly into stage groups
    (B % P == 0), falling back to the broadcast ring for small/ragged
    batches (e.g. the engine's B=1 single-stream bucket).
    """
    n_stages = mesh.shape["pp"]
    n_tp = mesh.shape.get("tp", 1)
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def chunk(params, tokens, positions, k_pages, v_pages, block_tables,
              key, temps, top_k, top_p):
        B = tokens.shape[0]
        use_il = interleave is True or (
            interleave == "auto" and B % n_stages == 0)
        if use_il and B % n_stages:
            raise ValueError(f"interleaved pp decode needs batch divisible "
                             f"by pp={n_stages}, got {B}")
        body = _interleaved_chunk_body if use_il else _broadcast_chunk_body
        return body(cfg, n_stages, n_tp, perm, decode_chunk,
                    params, tokens, positions, k_pages, v_pages,
                    block_tables, key, temps, top_k, top_p)

    page_spec = pages.page_spec(mesh)
    sharded = shard_map(
        chunk, mesh=mesh,
        in_specs=(_param_specs(cfg), P(), P(), page_spec, page_spec, P(),
                  P(), P(), P(), P()),
        out_specs=(P(), page_spec, page_spec))
    return jax.jit(sharded, donate_argnums=(3, 4))


def make_pp_decode_chunk_interleaved(cfg: ModelConfig, mesh: Mesh,
                                     decode_chunk: int):
    """make_pp_decode_chunk with the lane-group interleave forced (the
    traced batch must divide by pp; group size derives from the traced
    shape)."""
    return make_pp_decode_chunk(cfg, mesh, decode_chunk, interleave=True)


def _interleaved_chunk_body(cfg, n_stages, n_tp, perm, decode_chunk,
                            params, tokens, positions, k_pages, v_pages,
                            block_tables, key, temps, top_k, top_p):
    K = decode_chunk
    stage = jax.lax.axis_index("pp")
    B = tokens.shape[0]
    Bg = B // n_stages
    keys = jax.random.split(key, n_stages * K)

    def grp(arr, g):
        return jax.lax.dynamic_slice_in_dim(arr, g * Bg, Bg, 0)

    def put(arr, val, g):
        return jax.lax.dynamic_update_slice_in_dim(arr, val, g * Bg, 0)

    def turn(t, carry):
        x, k_pages, v_pages, toks_out, cur_tok, pos = carry
        # -- stage-0 block: head + sample the incoming group's previous
        # token, then embed its next input (real on stage 0 only; the
        # pos update is schedule-driven, identical on every stage).
        g0 = t % n_stages
        j = t // n_stages
        do_sample = j >= 1
        h = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = _tp_full((h @ params["lm_head"]).astype(jnp.float32),
                          n_tp, axis=1)
        key_idx = jnp.clip(g0 * K + (j - 1), 0, n_stages * K - 1)
        tok = sample_tokens(logits, keys[key_idx], grp(temps, g0),
                            grp(top_k, g0), grp(top_p, g0))
        row_idx = jnp.clip(j - 1, 0, K - 1)
        row = jax.lax.dynamic_slice(
            toks_out, (row_idx, g0 * Bg), (1, Bg))[0]
        toks_out = jax.lax.dynamic_update_slice(
            toks_out, jnp.where(do_sample, tok, row)[None],
            (row_idx, g0 * Bg))
        cur_g = jnp.where(do_sample, tok, grp(tokens, g0))
        cur_tok = put(cur_tok, cur_g, g0)
        pos = jnp.where(do_sample, put(pos, grp(pos, g0) + 1, g0), pos)
        x_in = _tp_full(params["embed"][grp(cur_tok, g0)], n_tp, axis=1)
        x = jnp.where(stage == 0, x_in, x)
        # -- slab: this stage's current group.
        gs = jnp.mod(t - stage, n_stages)
        i_s = (t - stage) // n_stages
        active = (t >= stage) & (i_s < K)
        pos_g = grp(pos, gs)
        tables_g = grp(block_tables, gs)
        blk_idx, slot = pages.token_slots(k_pages, tables_g, pos_g)
        eff_blk = jnp.where(active, blk_idx, pages.TRASH_BLOCK)

        x, k_pages, v_pages = _decode_slab(cfg, params, x, k_pages, v_pages,
                                           tables_g, pos_g, eff_blk, slot)
        x = jax.lax.ppermute(x, "pp", perm)
        return x, k_pages, v_pages, toks_out, cur_tok, pos

    zero = jnp.zeros((Bg, params["embed"].shape[1] * n_tp),
                     params["embed"].dtype)
    x = jax.lax.pcast(zero, 'pp', to='varying')
    toks_out = jax.lax.pcast(jnp.zeros((K, B), jnp.int32), 'pp',
                             to='varying')
    cur_tok = jax.lax.pcast(tokens, 'pp', to='varying')
    pos = positions
    x, k_pages, v_pages, toks_out, _, _ = jax.lax.fori_loop(
        0, K * n_stages + n_stages, turn,
        (x, k_pages, v_pages, toks_out, cur_tok, pos))
    toks_out = jax.lax.psum(
        jnp.where(stage == 0, toks_out, jnp.zeros_like(toks_out)), "pp")
    return toks_out, k_pages, v_pages



def _tp_block(cfg: ModelConfig, lp, x, cos, sin, positions):
    """llama._layer with the TP collectives explicit (shard_map body form):
    local head slices, psum over tp after wo and over (tp, ep) after the
    FFN. Returns (x, k, v) with k/v carrying the LOCAL kv-head slice (pages
    are tp-sharded on that axis)."""
    B, S, _ = x.shape
    Dh = cfg.head_dim
    h = rms_norm(x, lp["ln_attn"], cfg.norm_eps)
    q = (h @ lp["wq"]).reshape(B, S, -1, Dh)
    k = (h @ lp["wk"]).reshape(B, S, -1, Dh)
    v = (h @ lp["wv"]).reshape(B, S, -1, Dh)
    q, k = llama.qk_normed(cfg, lp, q, k)
    q = llama.apply_rope(q, cos, sin)
    k = llama.apply_rope(k, cos, sin)
    attn = llama.causal_attention(q, k, v, q_positions=positions,
                                  kv_positions=positions)
    x = x + jax.lax.psum(attn.reshape(B, S, -1) @ lp["wo"], "tp")
    h = rms_norm(x, lp["ln_mlp"], cfg.norm_eps)
    x = x + _ffn_psum(cfg, lp, h)
    return x, k, v


def make_pp_prefill(cfg: ModelConfig, mesh: Mesh, bucket: int,
                    mm: bool = False):
    """Drop-in for TpuEngine._prefill_fn(bucket) under pp(+tp): ring prefill
    with per-stage KV scatter + fused first-token sampling. With ``mm``,
    takes (mm_embeds, mm_positions) after seq_len and splices the encoder
    vectors over the placeholder-token embeddings before the ring (the
    multimodal injection of llama.forward:182-185, replicated on every
    stage — the splice is part of the embedding, which all stages compute
    identically)."""
    n_stages = mesh.shape["pp"]
    n_tp = mesh.shape.get("tp", 1)
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def prefill(params, tokens, seq_len, k_pages, v_pages, block_table_row,
                key, temps, top_k, top_p, mm_embeds=None, mm_positions=None):
        stage = jax.lax.axis_index("pp")
        S = tokens.shape[1]
        assert S == bucket, f"prefill traced at S={S}, keyed as bucket={bucket}"
        Dh = cfg.head_dim
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :],
                                     (1, S))
        cos, sin = rope_table(positions, Dh, cfg.rope_theta)
        blk_for_t, slot_for_t = pages.sequence_slots(
            k_pages, block_table_row, seq_len, S)

        x0 = _tp_full(params["embed"][tokens], n_tp, axis=2)  # [1, S, D]
        if mm_embeds is not None:
            x0 = x0.at[jnp.arange(1)[:, None], mm_positions].set(
                mm_embeds.astype(x0.dtype), mode="drop")
        zero = jnp.zeros_like(x0)

        def slab(x, k_pages, v_pages, active):
            def body(x, lp):
                x, k, v = _tp_block(cfg, lp, x, cos, sin, positions)
                return x, (k, v)

            x, (k_new, v_new) = jax.lax.scan(body, x, params["layers"])
            eff_blk = jnp.where(active, blk_for_t, pages.TRASH_BLOCK)
            k_pages, v_pages = pages.write(k_pages, v_pages, k_new, v_new,
                                           eff_blk, slot_for_t)
            return x, k_pages, v_pages

        def turn(tn, carry):
            x, k_pages, v_pages = carry
            x = jnp.where(stage == 0, jnp.where(tn == 0, x0, x), x)
            x, k_pages, v_pages = slab(x, k_pages, v_pages, active=stage == tn)
            x = jax.lax.ppermute(x, "pp", perm)
            return x, k_pages, v_pages

        x = jax.lax.pcast(zero, 'pp', to='varying')
        x, k_pages, v_pages = jax.lax.fori_loop(
            0, n_stages, turn, (x, k_pages, v_pages))
        x = jax.lax.psum(jnp.where(stage == 0, x, jnp.zeros_like(x)), "pp")
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        last = jnp.take_along_axis(x, (seq_len - 1)[:, None, None],
                                   axis=1)[:, 0]
        logits = _tp_full((last @ params["lm_head"]).astype(jnp.float32),
                          n_tp, axis=1)
        tok = sample_tokens(logits, key, temps, top_k, top_p)
        return tok, k_pages, v_pages

    page_spec = pages.page_spec(mesh)
    if mm:
        def prefill_mm(params, tokens, seq_len, mm_embeds, mm_positions,
                       k_pages, v_pages, block_table_row, key, temps, top_k,
                       top_p):
            # Engine mm calling convention (core.py _op_mm_prefill).
            return prefill(params, tokens, seq_len, k_pages, v_pages,
                           block_table_row, key, temps, top_k, top_p,
                           mm_embeds, mm_positions)

        sharded = shard_map(
            prefill_mm, mesh=mesh,
            in_specs=(_param_specs(cfg), P(), P(), P(), P(), page_spec,
                      page_spec, P(), P(), P(), P(), P()),
            out_specs=(P(), page_spec, page_spec))
        return jax.jit(sharded, donate_argnums=(5, 6))
    sharded = shard_map(
        prefill, mesh=mesh,
        in_specs=(_param_specs(cfg), P(), P(), page_spec, page_spec, P(),
                  P(), P(), P(), P()),
        out_specs=(P(), page_spec, page_spec))
    return jax.jit(sharded, donate_argnums=(3, 4))


def make_pp_prefill_with_prefix(cfg: ModelConfig, mesh: Mesh,
                                suffix_bucket: int, prefix_bucket: int):
    """Drop-in for TpuEngine._prefix_prefill_fn under pp(+tp): ring prefill
    continuing from cached prefix KV (llama.prefill_with_prefix:250-324, the
    automatic-prefix-caching hit path), so pp engines keep the prefix cache
    instead of disabling it (VERDICT r2 missing #7).

    Each stage's slab gathers ITS layers' cached prefix from its local page
    shard (layer axis on ``pp``, kv heads on ``tp`` — the gather is
    collective-free), the suffix attends to prefix+itself causally, and the
    suffix KV scatters at offset positions with the usual off-turn
    trash-redirect. The prior window is bounded by ``prefix_bucket`` blocks
    so a hit costs O(prefix)."""
    n_stages = mesh.shape["pp"]
    n_tp = mesh.shape.get("tp", 1)
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def prefill(params, tokens, suffix_len, prefix_len, k_pages, v_pages,
                block_table_row, prior_table_row, key, temps, top_k, top_p):
        stage = jax.lax.axis_index("pp")
        S = tokens.shape[1]
        assert S == suffix_bucket, (
            f"prefix prefill traced at S={S}, keyed as bucket={suffix_bucket}")
        T = prior_table_row.shape[1] * pages.block_size(k_pages)
        Dh = cfg.head_dim

        positions = (prefix_len[:, None]
                     + jnp.arange(S, dtype=jnp.int32)[None, :])      # [1,S]
        cos, sin = rope_table(positions, Dh, cfg.rope_theta)
        suffix_valid = jnp.arange(S)[None, :] < suffix_len[:, None]
        prior_pos = jnp.broadcast_to(
            jnp.arange(T, dtype=jnp.int32)[None, :], (1, T))
        prior_valid = prior_pos < prefix_len[:, None]
        kv_positions = jnp.concatenate([prior_pos, positions], axis=1)
        kv_valid = jnp.concatenate([prior_valid, suffix_valid], axis=1)

        blk_for_t, slot_for_t = pages.sequence_slots(
            k_pages, block_table_row, suffix_len, S, start=prefix_len)

        x0 = _tp_full(params["embed"][tokens], n_tp, axis=2)  # [1, S, D]
        zero = jnp.zeros_like(x0)

        def slab(x, k_pages, v_pages, active):
            def body(x, layer_in):
                lp, kp, vp = layer_in
                h = rms_norm(x, lp["ln_attn"], cfg.norm_eps)
                q = (h @ lp["wq"]).reshape(1, S, -1, Dh)      # local heads
                k = (h @ lp["wk"]).reshape(1, S, -1, Dh)
                v = (h @ lp["wv"]).reshape(1, S, -1, Dh)
                q, k = llama.qk_normed(cfg, lp, q, k)
                q = llama.apply_rope(q, cos, sin)
                k = llama.apply_rope(k, cos, sin)
                k_prior, v_prior = pages.read_prefix(kp, vp,
                                                     prior_table_row)
                attn = llama.causal_attention(
                    q, jnp.concatenate([k_prior, k], axis=1),
                    jnp.concatenate([v_prior, v], axis=1),
                    q_positions=positions, kv_positions=kv_positions,
                    kv_valid=kv_valid)
                x = x + jax.lax.psum(attn.reshape(1, S, -1) @ lp["wo"], "tp")
                h = rms_norm(x, lp["ln_mlp"], cfg.norm_eps)
                x = x + _ffn_psum(cfg, lp, h)
                return x, (k, v)

            x, (k_new, v_new) = jax.lax.scan(
                body, x, (params["layers"], k_pages, v_pages))
            eff_blk = jnp.where(active, blk_for_t, pages.TRASH_BLOCK)
            k_pages, v_pages = pages.write(k_pages, v_pages, k_new, v_new,
                                           eff_blk, slot_for_t)
            return x, k_pages, v_pages

        def turn(tn, carry):
            x, k_pages, v_pages = carry
            x = jnp.where(stage == 0, jnp.where(tn == 0, x0, x), x)
            x, k_pages, v_pages = slab(x, k_pages, v_pages, active=stage == tn)
            x = jax.lax.ppermute(x, "pp", perm)
            return x, k_pages, v_pages

        x = jax.lax.pcast(zero, 'pp', to='varying')
        x, k_pages, v_pages = jax.lax.fori_loop(
            0, n_stages, turn, (x, k_pages, v_pages))
        x = jax.lax.psum(jnp.where(stage == 0, x, jnp.zeros_like(x)), "pp")
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        last = jnp.take_along_axis(x, (suffix_len - 1)[:, None, None],
                                   axis=1)[:, 0]
        logits = _tp_full((last @ params["lm_head"]).astype(jnp.float32),
                          n_tp, axis=1)
        tok = sample_tokens(logits, key, temps, top_k, top_p)
        return tok, k_pages, v_pages

    page_spec = pages.page_spec(mesh)
    sharded = shard_map(
        prefill, mesh=mesh,
        in_specs=(_param_specs(cfg), P(), P(), P(), page_spec, page_spec,
                  P(), P(), P(), P(), P(), P()),
        out_specs=(P(), page_spec, page_spec))
    return jax.jit(sharded, donate_argnums=(4, 5))


def make_pp_embed(cfg: ModelConfig, mesh: Mesh, bucket: int):
    """Mean-pooled final-hidden embedding through the stage ring — the
    /v1/embeddings surface for pp(×tp×ep) engines (engine/core.py embed()).
    Same ring as prefill but no KV pages: each stage applies its slab,
    stage 0's wrap-around holds the final hidden, the pooled vector psums
    out replicated so every process can read it."""
    n_stages = mesh.shape["pp"]
    n_tp = mesh.shape.get("tp", 1)
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def embed(params, tokens, seq_len):
        stage = jax.lax.axis_index("pp")
        S = tokens.shape[1]
        assert S == bucket
        Dh = cfg.head_dim
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :],
                                     (1, S))
        cos, sin = rope_table(positions, Dh, cfg.rope_theta)
        x0 = _tp_full(params["embed"][tokens], n_tp, axis=2)  # [1, S, D]

        def slab(x):
            def body(x, lp):
                x, _, _ = _tp_block(cfg, lp, x, cos, sin, positions)
                return x, None

            x, _ = jax.lax.scan(body, x, params["layers"])
            return x

        def turn(tn, x):
            x = jnp.where(stage == 0, jnp.where(tn == 0, x0, x), x)
            x = slab(x)
            return jax.lax.ppermute(x, "pp", perm)

        x = jax.lax.pcast(jnp.zeros_like(x0), 'pp', to='varying')
        x = jax.lax.fori_loop(0, n_stages, turn, x)
        x = jax.lax.psum(jnp.where(stage == 0, x, jnp.zeros_like(x)), "pp")
        hidden = rms_norm(x, params["final_norm"],
                          cfg.norm_eps).astype(jnp.float32)
        mask = (jnp.arange(S) < seq_len[0])[None, :, None]
        pooled = (hidden * mask).sum(axis=1) / seq_len[0]
        return pooled[0]

    sharded = shard_map(
        embed, mesh=mesh,
        in_specs=(_param_specs(cfg), P(), P()),
        out_specs=P())
    return jax.jit(sharded)


def validate_pp(cfg: ModelConfig, pp: int, tp: int = 1, ep: int = 1) -> None:
    if cfg.n_layers % pp:
        raise ValueError(f"pp_size={pp} does not divide "
                         f"n_layers={cfg.n_layers}")
    if tp > 1:
        validate_tp(cfg, tp)
        if cfg.d_model % tp:  # embed shards the model dim under pp×tp
            raise ValueError(f"tp={tp} does not divide d_model={cfg.d_model}")
    if ep > 1:
        validate_tp(cfg, 1, ep)  # ep divisibility checks


def pp_param_shardings(cfg: ModelConfig, mesh: Mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), _param_specs(cfg),
                        is_leaf=lambda x: isinstance(x, P))


def shard_params_pp(params, cfg: ModelConfig, mesh: Mesh):
    """Lay unsharded params onto the (pp, tp, ep) serving mesh."""
    validate_pp(cfg, mesh.shape["pp"], mesh.shape.get("tp", 1),
                mesh.shape.get("ep", 1))
    shardings = pp_param_shardings(cfg, mesh)
    if any(d.process_index != jax.process_index() for d in mesh.devices.flat):
        # Multi-host mesh: device_put cannot target non-addressable devices;
        # route through a jitted identity (host inputs are treated as
        # replicated — every process feeds identical bytes — and
        # out_shardings lay down the per-process shards).
        return jax.jit(lambda p: p, out_shardings=shardings)(params)
    return jax.device_put(params, shardings)


def init_pp_params(cfg: ModelConfig, mesh: Mesh, key, dtype=None):
    validate_pp(cfg, mesh.shape["pp"], mesh.shape.get("tp", 1),
                mesh.shape.get("ep", 1))
    return jax.jit(lambda k: llama.init_params(cfg, k, dtype=dtype),
                   out_shardings=pp_param_shardings(cfg, mesh))(key)
