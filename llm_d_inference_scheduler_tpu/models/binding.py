"""What an engine binds of a model family, and what its programs count as.

``family(cfg)`` names the module whose ``init_params``, ``forward``,
``decode_step`` and ``prefill_with_prefix`` serve a configuration.
:func:`bind` is the other half of the seam: it decides, once, which form each
of the family's kernels takes on the device an engine is (the rules are the
kernels' own), and the value it returns answers what the engine asks of a
family afterwards: ``model_for(tokens)``, ``program_counts(...)`` (booked by
``EngineTelemetry.book_program``), ``pairs_per_row``, ``describe()``.

Nothing here imports ``engine/``. A new family brings its rules here, its
block to ``models/``, its kernels to ``ops/``, its cache to ``kvcache/`` and
its counters' declarations to ``engine/telemetry.py``.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import numpy as np

from ..ops import pallas_moe, pallas_ssm
from . import hybrid, llama, mla
from .configs import ModelConfig


def family(cfg: ModelConfig):
    """The module that holds ``cfg``'s block: ``init_params``, ``forward``,
    ``decode_step`` and ``prefill_with_prefix`` under one set of signatures.
    Latent attention (kv_lora_rank > 0) names models/mla.py, whose layer
    pattern, where it has one, says which of two kinds of attention a layer
    is; a layer pattern of mixers names models/hybrid.py, whose layers are
    state-space, expert and attention mixers in that pattern; everything else
    is models/llama.py's block, two kinds of K/V attention layer in one model
    (``kv_window``) among it."""
    if cfg.kv_lora_rank:
        return mla
    return hybrid if cfg.mixer_pattern else llama


def selection_counts(first: np.ndarray, n: np.ndarray, topk: int
                     ) -> dict[str, int]:
    """What runs of query tokens put through a block that selects the rows
    it attends to (``jetstream:dsa_*``). Run i is ``n[i]`` queries of contexts
    ``first[i]``, ``first[i]`` + 1, ...: a query of context c has c rows
    ``scored`` and min(c, topk) ``attended``, and is ``selected`` where c
    outnumbers ``topk``, else ``all``."""
    first, n = first.astype(np.int64), n.astype(np.int64)
    last = first + n - 1
    scored = int(np.sum((first + last) * n // 2))
    # The contexts beyond topk: a query of context c attends to topk rows
    # and leaves c - topk.
    lo = np.maximum(first, topk + 1)
    m = np.maximum(last - lo + 1, 0)
    left = int(np.sum((lo + last) * m // 2 - m * topk))
    return {"selected": int(m.sum()), "all": int((n - m).sum()),
            "scored": scored, "attended": scored - left}


def window_counts(first: np.ndarray, n: np.ndarray, window: int
                  ) -> dict[str, int]:
    """What the same runs put through a layer that attends to a window
    (``jetstream:swa_rows_total``): a query of context c has c rows of
    ``context`` and min(c, window) ``attended``."""
    got = selection_counts(first, n, window)
    return {"context": got["scored"], "attended": got["attended"]}


@dataclasses.dataclass(frozen=True)
class Bound:
    """A model as one engine serves it (:func:`bind`)."""

    module: Any             # family(mcfg)
    mcfg: ModelConfig       # the kernels' forms resolved, the FFN dense
    grouped: ModelConfig    # the same with the MoE FFN's grouped form
    chosen: ModelConfig     # the same, dense over the experts a row chose
    platform: str
    interpret: bool
    sharded: bool

    def _moe_facts(self) -> dict[str, Any]:
        """What both rules of the MoE FFN's form ask of the model and the
        engine, beside a program's rows."""
        m = self.mcfg
        return dict(n_experts=m.n_experts,
                    experts_per_token=m.experts_per_token,
                    d_model=m.moe_latent_dim or m.d_model,
                    d_ff=m.moe_d_ff or m.d_ff, platform=self.platform,
                    interpret=self.interpret, sharded=self.sharded)

    def moe_grouped(self, tokens: int) -> bool:
        """Whether a program of ``tokens`` rows (batch x sequence, padded)
        computes the chosen experts' rows alone."""
        return pallas_moe.use_grouped(tokens, **self._moe_facts())

    def moe_chosen(self, tokens: int) -> bool:
        """Whether a program of ``tokens`` rows reads the weights of the
        held experts some row chose, and no others."""
        return pallas_moe.use_chosen(
            tokens, router_outputs=self.mcfg.router_width,
            held=self.mcfg.held_experts[1], **self._moe_facts())

    def model_for(self, tokens: int) -> ModelConfig:
        """The model as a program of ``tokens`` rows (batch x sequence,
        padded) traces it: the MoE FFN in the form the shape calls for."""
        if self.moe_chosen(tokens):
            return self.chosen
        return self.grouped if self.moe_grouped(tokens) else self.mcfg

    def decode_expert_visits(self, rows: int) -> int:
        """(Held expert, expert layer) pairs one decode step of ``rows`` rows
        passes, where its program reads the chosen ones alone and counts
        them (``jetstream:moe_decode_experts_total``); 0 where it reads
        all."""
        m = self.mcfg
        return (m.held_experts[1] * m.n_expert_layers
                if self.moe_chosen(rows) else 0)

    @property
    def pairs_per_row(self) -> int:
        """(Token, expert) choices the routers make of one row of a step."""
        return self.mcfg.experts_per_token * self.mcfg.n_expert_layers

    def program_counts(self, kind: str, rows: int, steps: int, *,
                       real: int = 0,
                       queries: tuple[np.ndarray, np.ndarray] | None = None
                       ) -> list[tuple[str, str | None, int]]:
        """What one dispatched program runs as: (a name of
        ``engine/telemetry.PROGRAM_COUNTERS``, its label's value or None,
        the amount) each. ``kind`` is the engine's op, ``rows`` the rows
        (padded tokens) of one of its ``steps``; of a program that serves
        requests, ``real`` is how many of its sequences are somebody's and
        ``queries`` their runs of query tokens (:func:`selection_counts`)."""
        m, decode, tokens = self.mcfg, kind == "decode", rows * steps
        counts: list[tuple[str, str | None, int]] = []
        if m.n_experts:
            # Under the form its shape traced to (dense over the chosen
            # experts is dense over the experts, less the unchosen).
            counts.append(("moe_ffn_tokens", "grouped" if self.moe_grouped(
                rows) else "dense", tokens))
        if m.kv_lora_rank:
            # models/mla.py: one query a sequence is absorbed, a run of them
            # expanded.
            counts.append(("mla_attention_tokens",
                           "absorbed" if decode else "expanded", tokens))
            if not decode:
                # By the form the expanded attention traced with.
                counts.append(("mla_window_attention_tokens",
                               m.expanded_impl.split("_")[0], tokens))
            if m.index_topk and queries is not None:
                got = selection_counts(*queries, m.index_topk)
                counts += [("dsa_query_tokens", "selected", got["selected"]),
                           ("dsa_query_tokens", "all", got["all"]),
                           ("dsa_rows", "scored", got["scored"]),
                           ("dsa_rows", "attended", got["attended"])]
        if m.kv_window and kind == "prefix_prefill":
            # models/llama.py: a continuation window's queries against the
            # K/V pages before it, by the form that attention traced with
            # (a first window reads no page: it is not counted).
            counts.append(("kv_prefill_attention_tokens",
                           m.swa_impl.split("_")[0], tokens))
        if m.window and queries is not None:
            # Either family's window layers, from the queries' positions.
            counts += [("swa_rows", kind, amount) for kind, amount in
                       window_counts(*queries, m.window).items()]
        if m.n_state_layers:
            # models/hybrid.py: one position a sequence is the step form, a
            # run of them the scan form (a convolution's run form among
            # them); a first window starts its slots.
            counts.append(("ssm_tokens", "step" if decode else "scan",
                           tokens))
            if decode and m.n_recurrent_layers:
                # (Layers that keep a tail alone update no recurrent state.)
                counts.append(("ssm_state_updates", m.ssm_impl.split("_")[0],
                               tokens * m.n_state_layers))
            if kind == "prefill" and real:
                counts.append(("ssm_slot_prefills", None, real))
            if m.ssm_dt_rank and not decode:
                # A window's rows through the recurrence in order, by the
                # form that scan traced with.
                counts.append(("ssm_scan_tokens",
                               m.ssm_scan_impl.split("_")[0], tokens))
        return counts

    def describe(self) -> dict[str, Any]:
        """The family's part of /health's ``settings`` (every key on every
        engine; 0 or None where the model has no such thing)."""
        m = self.mcfg
        return {
            # A block that selects the rows it attends to (0: none).
            "index_topk": m.index_topk,
            "index_scores": m.index_impl if m.index_topk else None,
            # How a latent block's prefill and continuation windows attend:
            # the scores a tile at a time in VMEM, or whole (None: no latent
            # attention).
            "expanded_attention": (m.expanded_impl if m.kv_lora_rank
                                   else None),
            # The experts this chip holds of those its router scores, and
            # the router's outputs that compute nothing.
            "experts_first": m.held_experts[0],
            "experts_held": m.held_experts[1],
            "zero_experts": m.n_zero_experts,
            # Rows an expert can expect (rows x choices / router outputs) up
            # to which a program of one row tile reads the chosen held
            # experts alone (None: every program reads all it holds).
            "experts_chosen_max_rows": (
                pallas_moe.CHOSEN_MAX_ROWS_PER_EXPERT
                if self.moe_chosen(1) else None),
            # How a decode step fetches its slots' recurrent states (None:
            # no layer keeps one).
            "state_update": m.ssm_impl if m.n_recurrent_layers else None,
            # How a prompt window runs a recurrence that has no matrix form,
            # and the layers that keep a slot row -- such a state, or a
            # convolution's tail and nothing else (keys only such models
            # have).
            **({"state_scan": m.ssm_scan_impl} if m.ssm_dt_rank else {}),
            **({"state_layers": m.n_state_layers}
               if m.ssm_dt_rank or m.conv_mixers else {}),
            # The form of the layers that attend to a window of the context
            # (a key only a model with such layers has); in the K/V family
            # also that of a continuation window's attention, both kinds of
            # layer.
            **({"window_attention": m.swa_impl} if m.window else {}),
            # What models/llama.py's router reads and its experts' activation
            # where they are not Mixtral's (keys only such a model has).
            **({"router_input": m.router_input,
                "expert_activation": m.expert_act}
               if (m.router_input, m.expert_act) != ("ffn", "swiglu")
               else {}),
        }


def bind(mcfg: ModelConfig, *, platform: str, interpret: bool = False,
         sharded: bool = False, forced: dict[str, str] | None = None
         ) -> Bound:
    """``mcfg`` as an engine on ``platform`` serves it (an argument, not
    looked up: a rehearsal binds for "tpu" on a CPU host): ``interpret``
    where it runs its kernels through the interpreter (tests on the CPU),
    ``sharded`` where its weights or pools span devices. How a decode step
    fetches its slots' recurrent states (``ssm_impl``) is
    ``pallas_ssm.use_kernel``'s, and so is how a prompt window of a model
    whose recurrence has no matrix form runs it (``ssm_scan_impl``); the
    three forms of ops/pallas_dsa.py's kernels follow one rule, the kernel
    on a TPU, where a [heads, queries, rows] product must not reach HBM, and
    the plain form on the CPU: every
    latent block's expanded attention (``expanded_impl``: a prefill, a window
    that continues a cached prefix, whether or not the block selects), a
    selecting block's indexer (``index_impl``), and the layers that attend to
    a window (``swa_impl``); the MoE FFN's form is
    chosen per program (``Bound.model_for``). ``forced`` names form fields a
    caller sets over the rules (a comparison of two forms on one device)."""
    forms: dict[str, str] = {}
    if mcfg.n_recurrent_layers:
        # A slot's tile, [sublanes, lanes]: a head's [head_dim, state], or a
        # Mamba-1 layer's [state, inner].
        kernel = pallas_ssm.use_kernel(
            mcfg.ssm_row[-1], mcfg.ssm_row[-2], platform=platform,
            sharded=sharded, interpret=interpret)
        forms["ssm_impl"] = ("gathered" if not kernel else
                             "kernel_interpret" if interpret else "kernel")
        if mcfg.ssm_dt_rank:
            # The prompt windows' scan keeps the same tile resident.
            forms["ssm_scan_impl"] = ("xla" if not kernel else
                                      "kernel_interpret" if interpret
                                      else "kernel")
    tiled = ("kernel_interpret" if interpret else
             "kernel" if platform == "tpu" else "xla")
    if mcfg.kv_lora_rank:
        forms["expanded_impl"] = tiled
    if mcfg.index_topk:
        forms["index_impl"] = tiled
    if mcfg.window:
        # The window layers' kernels (the latent family's two: ops/
        # pallas_latent_attention.py's walk over the window's pages, ops/
        # pallas_dsa.py's tiles under the band; the K/V family's two: ops/
        # pallas_paged_attention.py's decode walk, and its tiled walk under
        # a continuation window's queries, which the full layers take too)
        # on a TPU, the plain forms on the CPU.
        # (A K/V page's DMA wants whole lanes: kvcache/pages.use_kernel.)
        aligned = mcfg.kv_lora_rank or interpret or mcfg.head_dim % 128 == 0
        forms["swa_impl"] = tiled if aligned else "xla"
    mcfg = dataclasses.replace(mcfg, **{**forms, **(forced or {})})
    suffix = "_interpret" if interpret else ""
    return Bound(
        module=family(mcfg), mcfg=mcfg,
        grouped=dataclasses.replace(mcfg, moe_impl="grouped" + suffix),
        chosen=dataclasses.replace(mcfg, moe_impl="chosen" + suffix),
        platform=platform, interpret=interpret, sharded=sharded)
