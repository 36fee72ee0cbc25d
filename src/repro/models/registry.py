"""Arch registry: config -> a uniform Model interface for every family.

The Model bundle is what the training loop, serving engine and dry-run all
consume; it hides family differences (enc-dec inputs, recurrent caches, MoE
aux losses) behind five functions.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import moe, transformer, whisper, xlstm, zamba

Params = Any
Batch = Dict[str, jax.Array]


@dataclasses.dataclass(frozen=True)
class Model:
    config: ModelConfig
    init: Callable[[jax.Array], Params]
    forward: Callable[..., Tuple[jax.Array, Dict[str, jax.Array]]]
    init_cache: Callable[..., Any]
    # decode_step(params, tokens (B, T), cache, pos) -> (logits (B, T, V),
    # cache).  T is 1 on the steady-state serving path; the bounded
    # multi-token form (token t of row b at position pos[b] + t) is the
    # speculative-verification step (serving/speculate.py) — all K+1 draft
    # positions scored in ONE forward.
    decode_step: Callable[..., Tuple[jax.Array, Any]]
    # prefill(params, tokens (1, S), cache, slot, length) -> (logits (1, V)
    # at position length-1, cache with slot's rows written in one shot).
    # The bulk-prefill path of the serving engine: one call per admitted
    # prompt instead of one decode step per prompt token.
    prefill: Callable[..., Tuple[jax.Array, Any]]
    head_matrix: Callable[[Params], jax.Array]
    input_fields: Tuple[str, ...]   # batch keys consumed by forward
    # whether prefill tolerates right-padded token buffers (attention masks
    # padded positions out; recurrent families consume every token and must
    # be prefilled at the exact prompt length)
    padded_prefill: bool = True
    # paged-KV serving (serving/kv_cache.py): attention families expose a
    # page-pool cache plus block-table prefill/decode; recurrent families
    # (xlstm/zamba — O(1) SSD/LSTM state) leave these None and the engine
    # falls back to the dense slot-addressed cache.
    # init_paged_cache(num_pages, page_size, slots, max_len, dtype)
    init_paged_cache: Optional[Callable[..., Any]] = None
    # prefill_paged(params, tokens (1, S), cache, pages, slot, length)
    prefill_paged: Optional[Callable[..., Tuple[jax.Array, Any]]] = None
    # decode_paged(params, tokens (B, T), cache, pos (B,), block_tables) —
    # T = 1 steady state, K+1 for a speculative verify (multi-token rows
    # commit via kv_cache.commit_tokens; past-table positions -> scratch)
    decode_paged: Optional[Callable[..., Tuple[jax.Array, Any]]] = None
    # whether decode_paged reads K/V pages in place on single-token steps
    # (kv_cache.reads_in_place; the dense family) — the others gather
    paged_in_place: bool = False

    @property
    def supports_paged(self) -> bool:
        return (self.init_paged_cache is not None
                and self.prefill_paged is not None
                and self.decode_paged is not None)

    def make_inputs(self, rng, batch: int, seq: int) -> Batch:
        """Concrete (random) inputs for smoke tests."""
        cfg = self.config
        out: Batch = {}
        n_img = cfg.num_image_tokens
        for f in self.input_fields:
            if f == "tokens":
                s = seq - n_img if (n_img and "patch_embeds" in self.input_fields) else seq
                out["tokens"] = jax.random.randint(rng, (batch, s), 0,
                                                   cfg.vocab_size, jnp.int32)
            elif f == "patch_embeds":
                out["patch_embeds"] = jax.random.normal(
                    rng, (batch, n_img, cfg.d_model), jnp.float32)
            elif f == "frames":
                out["frames"] = jax.random.normal(
                    rng, (batch, seq, cfg.d_model), jnp.float32)
        return out


_FAMILIES = {
    "dense": transformer,
    "moe": moe,
    "whisper": whisper,
    "xlstm": xlstm,
    "zamba": zamba,
}


def build(cfg: ModelConfig) -> Model:
    mod = _FAMILIES[cfg.family]
    fields: Tuple[str, ...] = ("tokens",)
    if cfg.family == "whisper":
        fields = ("frames", "tokens")
    elif cfg.num_image_tokens:
        fields = ("tokens", "patch_embeds")
    paged_kw: Dict[str, Any] = {}
    if hasattr(mod, "init_paged_cache"):
        paged_kw = dict(
            init_paged_cache=(
                lambda num_pages, page_size, slots, max_len,
                dtype=jnp.bfloat16: mod.init_paged_cache(
                    cfg, num_pages, page_size, slots, max_len, dtype)),
            prefill_paged=(
                lambda params, tokens, cache, pages, slot, length:
                mod.prefill_paged(cfg, params, tokens, cache, pages, slot,
                                  length)),
            decode_paged=(
                lambda params, tokens, cache, pos, block_tables:
                mod.decode_paged(cfg, params, tokens, cache, pos,
                                 block_tables)),
        )
    return Model(
        config=cfg,
        init=lambda key: mod.init(cfg, key),
        forward=lambda params, batch, **kw: mod.forward(cfg, params, batch, **kw),
        init_cache=lambda batch, max_len, dtype=jnp.bfloat16: mod.init_cache(
            cfg, batch, max_len, dtype),
        decode_step=lambda params, tokens, cache, pos: mod.decode_step(
            cfg, params, tokens, cache, pos),
        prefill=lambda params, tokens, cache, slot, length: mod.prefill(
            cfg, params, tokens, cache, slot, length),
        head_matrix=lambda params: mod.head_matrix(cfg, params),
        input_fields=fields,
        # recurrent state consumes every token: exact-length prefill there;
        # moe pads like dense (its serving expert layer drops no token)
        padded_prefill=cfg.family not in ("xlstm", "zamba"),
        paged_in_place=cfg.family == "dense",
        **paged_kw,
    )
