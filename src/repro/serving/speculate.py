"""Self-speculative decoding: low-bit FORMS drafts verified on the paged
serving engine (DESIGN.md §6e).

FORMS's premise is that aggressive weight compression — polarized fragments
with low-bit magnitude codes — preserves accuracy at a fraction of the
compute/storage cost.  That means every served model already contains its
own *draft model*: re-quantizing the target's weights at 4-bit magnitudes
(optionally on larger fragments, optionally keeping only every n-th layer)
manufactures a cheap approximation for zero extra checkpoint cost.  This
module turns that into serving latency:

* :func:`make_draft_tree` / :func:`make_draft` — derive the draft pytree
  from the target's weights through the existing ``repro.forms``
  ``compress_tree``/``FormsSpec`` machinery (``mode="forms"``) or the
  generalized int8/int4 serving quantizer (``mode="int"``,
  serving/quant_weights.py — one code path for draft weights and the
  existing int8 serving path).
* :class:`SpeculativeRunner` — wraps the engine's :class:`ModelRunner` with
  a draft-K-tokens → verify-in-one-target-call loop.  One jitted dispatch
  per round: an inner ``lax.scan`` decodes K+1 draft tokens on the draft's
  own paged cache, the target scores all K+1 positions in a single bounded
  multi-token paged-attention forward, and acceptance runs on device —
  exact greedy acceptance (token-identical to the non-speculative engine)
  or temperature-mode rejection sampling that provably matches the target
  distribution (:func:`rejection_outcome_probs`).
* Per-slot **adaptive K** — an acceptance EWMA per slot shrinks the
  eligible draft length when acceptance drops and grows it back when the
  draft is hot; the jitted shapes stay fixed at ``k`` (the eligibility
  vector is a plain int32 argument, so adaptation never retraces).

Rollback protocol (DESIGN.md §6e): a round tentatively commits K+1 rows at
``pos..pos+K`` into the target's page pool (and K+1 draft rows at
``pos..pos+K``).  When verification accepts only ``n``, the host rewinds
its write cursor to ``pos+n+1`` — the positional rollback.  Rejected rows
release their page slots implicitly: every decode mask admits only
``kpos <= pos`` rows and every row is rewritten before its position can
enter a mask, exactly the invariant the dense engine relies on for padded
prefill buckets.  ``kv_cache.rollback_tokens`` additionally scrubs the
rejected rows for debugging/auditing (the engine does not need it on the
hot path).  The draft cache shares the target's block tables and page
geometry, so the two pools stay position-synced by construction; the draft
scan runs one extra step so a fully-accepted round still leaves the draft's
row for ``d_K`` written.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.forms import (CompressReport, FormsLinearParams, FormsSpec,
                         compress_tree, decompress_tree, default_spec)
from repro.forms import sparsity_stats as forms_sparsity_stats
from repro.models.registry import Model, build
from repro.serving.quant_weights import quantize_tree


@dataclasses.dataclass(frozen=True)
class SpeculateConfig:
    """Static description of one speculative-decoding configuration.

    k: max draft tokens verified per round (the jitted verify width is k+1).
    bits: draft magnitude bits (4 = the paper's low-bit sub-array regime).
    mode: "forms" (compress_tree at ``bits``/``fragment``) or "int"
      (serving/quant_weights symmetric int grid — shares the int8 path).
    fragment: forms-mode fragment size m; None keeps the target's geometry
      (sign elections stay stable, which is what keeps acceptance high when
      the target itself serves compressed).
    layer_step: keep every ``layer_step``-th block layer in the draft (1 =
      full depth).  Evenly-spaced early-exit drafts suit trained models with
      layer redundancy; untrained/random weights need full depth.
    adaptive / k_min / low / high / ewma: per-slot adaptive-K policy — an
      acceptance-rate EWMA per slot; below ``low`` the slot's eligible K
      shrinks by one (floor ``k_min``), above ``high`` it grows back
      (ceiling ``k``).  A round's jitted width follows the MAX eligible K
      over the active slots (one compiled variant per width, like prefill
      buckets), so cold drafts really do cost fewer draft/verify steps.
    """

    k: int = 4
    bits: int = 4
    mode: str = "forms"
    fragment: Optional[int] = None
    layer_step: int = 1
    adaptive: bool = True
    k_min: int = 1
    low: float = 0.4
    high: float = 0.8
    ewma: float = 0.5

    def __post_init__(self):
        if self.k < 1:
            raise ValueError(f"draft k must be >= 1, got {self.k}")
        if self.mode not in ("forms", "int"):
            raise ValueError(f"draft mode must be 'forms' or 'int', "
                             f"got {self.mode!r}")
        if self.layer_step < 1:
            raise ValueError(f"layer_step must be >= 1, got {self.layer_step}")
        if not 1 <= self.k_min <= self.k:
            raise ValueError(f"k_min={self.k_min} must be in [1, k={self.k}]")


# ---------------------------------------------------------------------------
# draft derivation
# ---------------------------------------------------------------------------


def _is_forms(x) -> bool:
    return isinstance(x, FormsLinearParams)


def _has_forms_leaves(params: Any) -> bool:
    return any(_is_forms(l) for l in
               jax.tree_util.tree_leaves(params, is_leaf=_is_forms))


def skip_layers(model: Model, params: Any, layer_step: int
                ) -> Tuple[Model, Any]:
    """Keep every ``layer_step``-th scan-stacked block layer (always
    including layer 0) — the structural half of a self-drafted model.

    Slices the leading layer axis of every leaf under the stacked block
    collections (``blocks``; whisper's decoder ``dec_blocks`` — its encoder
    runs only at prefill admission and keeps full depth) and rebuilds the
    family ``Model`` at the reduced ``num_layers``.  Works on dense and
    FORMS-compressed trees alike (compressed leaves slice their
    mags/signs/scale together).
    """
    if layer_step <= 1:
        return model, params
    cfg = model.config
    if cfg.first_k_dense_replace:
        raise ValueError("layer skipping keeps one stacked group; this "
                         "model has leading dense layers in another")
    keep = jnp.asarray(list(range(0, cfg.num_layers, layer_step)))
    out = dict(params)
    for name in ("blocks", "dec_blocks"):
        if name in out:
            out[name] = jax.tree_util.tree_map(lambda a: a[keep], out[name])
    return build(dataclasses.replace(cfg, num_layers=int(keep.shape[0]))), out


def make_draft_tree(params: Any, spec: Optional[FormsSpec] = None, *,
                    bits: int = 4, mode: str = "forms",
                    ctx: Optional[Any] = None,
                    plan: Optional[Dict[str, FormsSpec]] = None
                    ) -> Tuple[Any, CompressReport]:
    """Derive a low-bit draft pytree from the target's weights.

    ``mode="forms"`` routes through ``repro.forms.compress_tree`` at ``spec``
    (default: ``FormsSpec(bits=bits)``) — uint8 low-bit magnitudes + fragment
    signs, served through the polarized-matmul kernel exactly like a
    compressed target.  ``mode="int"`` routes through the generalized
    ``serving.quant_weights.quantize_tree(bits=...)`` symmetric int grid —
    the same code path as the existing int8 serving weights.

    An already-compressed target is reconstructed first (``compress_tree``
    is idempotent on ``FormsLinearParams`` leaves, so a 4-bit draft of an
    8-bit tree must re-quantize the float projection, not alias the 8-bit
    leaves).  ``plan`` makes the draft heterogeneous: a ``{path:
    FormsSpec}`` per-leaf override map (``forms.autobits.plan_draft_bits``
    derives one at the modeled cost of the uniform ``bits`` draft).
    Returns ``(tree, CompressReport)``.
    """
    if _has_forms_leaves(params):
        params = decompress_tree(params)
    if mode == "int":
        if plan is not None:
            raise ValueError("per-leaf plans are a forms-mode feature; "
                             "mode='int' drafts are uniform")
        tree, before, after = quantize_tree(params, bits=bits)
        return tree, CompressReport(errors={}, bytes_dense=before,
                                    bytes_compressed=after)
    if mode != "forms":
        raise ValueError(f"draft mode must be 'forms' or 'int', got {mode!r}")
    spec = spec if spec is not None else FormsSpec(bits=bits)
    return compress_tree(params, spec, ctx=ctx, plan=plan)


def make_draft(model: Model, params: Any, cfg: SpeculateConfig, *,
               ctx: Optional[Any] = None,
               plan: Optional[Dict[str, FormsSpec]] = None
               ) -> Tuple[Model, Any, CompressReport]:
    """Full draft derivation: optional layer skipping + low-bit weights.

    Returns ``(draft_model, draft_params, report)``.  The float projection
    of a compressed target is reconstructed before slicing so the draft
    approximates what the target actually serves.  ``plan`` rides through
    to :func:`make_draft_tree` — an allocator-derived per-leaf bits map
    replaces the uniform ``cfg.bits`` quantization (``plan`` lives outside
    :class:`SpeculateConfig` because the config is a frozen hashable the
    jitted rounds key on, and the plan is per-tree data, not policy).
    """
    if _has_forms_leaves(params):
        params = decompress_tree(params)
    draft_model, draft_params = skip_layers(model, params, cfg.layer_step)
    spec = (FormsSpec(m=cfg.fragment, bits=cfg.bits)
            if cfg.fragment is not None else FormsSpec(bits=cfg.bits))
    draft_params, report = make_draft_tree(draft_params, spec, bits=cfg.bits,
                                           mode=cfg.mode, ctx=ctx, plan=plan)
    return draft_model, draft_params, report


# ---------------------------------------------------------------------------
# rejection-sampling math (shared by the runner and the property tests)
# ---------------------------------------------------------------------------


def residual_distribution(p: jax.Array, q: jax.Array) -> jax.Array:
    """The resample distribution after a rejection: ``norm(max(p - q, 0))``.

    Falls back to ``p`` when the residual mass is ~0 (p == q): rejection
    probability is 0 there, so the fallback only guards float noise.
    """
    res = jnp.maximum(p - q, 0.0)
    tot = res.sum(-1, keepdims=True)
    return jnp.where(tot > 1e-9, res / jnp.maximum(tot, 1e-20), p)


def rejection_outcome_probs(p: jax.Array, q: jax.Array) -> jax.Array:
    """Closed-form next-token distribution of one speculative accept step.

    Draw x ~ q, accept with prob min(1, p(x)/q(x)), else resample from
    :func:`residual_distribution`.  The induced distribution is

        q(x) * min(1, p(x)/q(x)) + (1 - sum_y min(p(y), q(y))) * residual(x)

    which equals ``p`` exactly — the identity the hypothesis property test
    asserts against these same helpers the runner samples through.
    """
    accept = jnp.minimum(p, q)
    rej = 1.0 - accept.sum(-1, keepdims=True)
    return accept + rej * residual_distribution(p, q)


def _accept(logits_t: jax.Array, draft_lg: jax.Array, drafts: jax.Array,
            k_eligible: jax.Array, temps: jax.Array, key: jax.Array
            ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Vectorized per-slot draft acceptance (device side).

    logits_t: (B, K+1, V) target logits at positions pos..pos+K (f32);
    draft_lg: (K, B, V) the draft logits each draft token was sampled from;
    drafts: (K, B) draft tokens d_1..d_K; k_eligible: (B,) per-slot draft
    budget this round (adaptive K); temps: (B,) per-slot temperatures.

    Greedy rows (temp <= 0) accept d_i iff it IS the target argmax and
    correct with the argmax — the emitted sequence is exactly the
    non-speculative greedy rollout.  Temperature rows accept d_i with prob
    min(1, p_i(d)/q_i(d)) and correct from the residual distribution; a
    fully-accepted row takes its bonus token from the target's K+1-th
    logits.  Returns (out (B, K+1) emitted-token grid, n_emit (B,), key).
    """
    kk, b = drafts.shape
    drafts_bt = drafts.T                                     # (B, K)
    lg_d = jnp.moveaxis(draft_lg, 0, 1)                      # (B, K, V)
    greedy = temps <= 0.0
    safe_t = jnp.maximum(temps, 1e-6)
    t_arg = jnp.argmax(logits_t, axis=-1).astype(jnp.int32)  # (B, K+1)

    acc_greedy = t_arg[:, :kk] == drafts_bt
    p = jax.nn.softmax(logits_t[:, :kk] / safe_t[:, None, None], axis=-1)
    q = jax.nn.softmax(lg_d / safe_t[:, None, None], axis=-1)
    p_d = jnp.take_along_axis(p, drafts_bt[..., None], axis=-1)[..., 0]
    q_d = jnp.take_along_axis(q, drafts_bt[..., None], axis=-1)[..., 0]
    key, ku, kr = jax.random.split(key, 3)
    u = jax.random.uniform(ku, (b, kk))
    acc_temp = u * q_d < p_d          # u < p/q, with the q>0 guard folded in

    accept = jnp.where(greedy[:, None], acc_greedy, acc_temp)
    accept = jnp.logical_and(accept,
                             jnp.arange(kk)[None, :] < k_eligible[:, None])
    # leading-accept count: cumprod zeroes everything after the first reject
    n_acc = jnp.sum(jnp.cumprod(accept.astype(jnp.int32), axis=1), axis=1)

    bidx = jnp.arange(b)
    lg_j = logits_t[bidx, n_acc]                             # (B, V)
    p_j = jax.nn.softmax(lg_j / safe_t[:, None], axis=-1)
    # q at the correction index; zero past the eligible drafts, so the
    # residual reduces to p (the bonus token samples the full target dist)
    q_j = jnp.where((n_acc < k_eligible)[:, None],
                    q[bidx, jnp.minimum(n_acc, kk - 1)], 0.0)
    res = residual_distribution(p_j, q_j)
    corr_temp = jax.random.categorical(
        kr, jnp.log(jnp.maximum(res, 1e-20))).astype(jnp.int32)
    corr = jnp.where(greedy, t_arg[bidx, n_acc], corr_temp)

    idx = jnp.arange(kk + 1)[None, :]
    drafts_pad = jnp.concatenate([drafts_bt, jnp.zeros((b, 1), jnp.int32)],
                                 axis=1)
    out = jnp.where(idx < n_acc[:, None], drafts_pad, corr[:, None])
    return out, n_acc + 1, key


# ---------------------------------------------------------------------------
# the runner
# ---------------------------------------------------------------------------

# imported late to avoid a module cycle (engine imports this module from
# inside ServingEngine.__init__)
from repro.serving.engine import ModelRunner, _sample_on_device  # noqa: E402


@dataclasses.dataclass
class SlotSpecState:
    """Host-side adaptive-K state of one serving slot."""

    k: int
    ewma: float = 1.0


class SpeculativeRunner(ModelRunner):
    """A :class:`ModelRunner` whose decode round is draft-K → verify-once.

    The target side is the plain runner (same donation, mesh path, prefill
    buckets).  On top of it the speculative round runs as ONE jitted
    dispatch per round:

    1. an inner ``lax.scan`` decodes ``k+1`` draft tokens on the draft's own
       paged cache (same block tables/page geometry as the target — the two
       pools stay position-synced by construction);
    2. the target scores all ``k+1`` positions in a single bounded
       multi-token paged decode (``Model.decode_paged`` with (B, K+1)
       tokens), tentatively committing their K/V rows;
    3. acceptance (greedy-exact or rejection sampling) runs on device and
       returns the emitted-token grid plus per-slot emit counts — the only
       host sync of the round.

    Both caches are donated; admission prefills BOTH caches (one extra
    jitted draft prefill per admit).  Per-slot adaptive K lives on the
    host: the round's WIDTH is the max eligible K over the active slots
    (one compiled step per width, bucketed like prefill, so shrinking K
    actually removes draft scan steps and verify columns), and the
    per-slot eligibility vector enters the jitted step as a plain int32
    argument (no retrace when only the mix of slots changes).
    """

    def __init__(self, model: Model, params: Any, cache: Any, *,
                 draft_model: Model, draft_params: Any, draft_cache: Any,
                 spec_cfg: SpeculateConfig,
                 draft_cache_shardings: Any = None, **kw):
        super().__init__(model, params, cache, **kw)
        if not self.paged:
            raise ValueError(
                "speculative decoding needs the paged cache (the verify "
                "step is a bounded multi-token paged decode); recurrent "
                "families fall back to the plain engine")
        self.draft_model = draft_model
        self.draft_params = draft_params
        self.draft_cache = draft_cache
        self.spec_cfg = spec_cfg
        self.k_max = spec_cfg.k
        self.draft_cache_shardings = draft_cache_shardings
        self._slots: Dict[int, SlotSpecState] = {}
        self.rounds = 0
        self.participations = 0   # active-slot round participations
        self.drafted = 0
        self.accepted = 0
        self.emitted = 0
        self._draft_prefill_fns: Dict[int, Any] = {}
        self._draft_chunk_fns: Dict[int, Any] = {}
        self._spec_steps: Dict[int, Any] = {}

    def _get_spec_step(self, kk: int):
        """The jitted round at width ``kk`` (the max eligible K of the
        active slots this round) — one compiled variant per width, like
        prefill buckets, so adaptive K removes real draft/verify compute."""
        fn = self._spec_steps.get(kk)
        if fn is None:
            kw_shard: Dict[str, Any] = {}
            if self.ctx is not None:
                from jax.sharding import NamedSharding, PartitionSpec
                replicated = NamedSharding(self.ctx.mesh, PartitionSpec())
                kw_shard["out_shardings"] = (replicated, replicated,
                                             self.cache_shardings,
                                             self.draft_cache_shardings)
            fn = jax.jit(functools.partial(self._speculate_impl, kk),
                         donate_argnums=(1, 3) if self.donate else (),
                         **kw_shard)
            self._spec_steps[kk] = fn
        return fn

    # -- the jitted round ------------------------------------------------

    def _speculate_impl(self, kk, p_t, c_t, p_d, c_d, toks, pos, tables,
                        k_eligible, temps, key):
        with default_spec(self.spec), forms_sparsity_stats(self.meter):

            def draft_body(carry, _):
                tok, c, dpos, key = carry
                logits, c = self.draft_model.decode_paged(p_d, tok[:, None],
                                                          c, dpos, tables)
                lg = logits[:, 0].astype(jnp.float32)
                key, sub = jax.random.split(key)
                nxt = _sample_on_device(lg, temps, sub)
                return (nxt, c, dpos + 1, key), (nxt, lg)

            # k+1 draft steps: the extra step only exists to write the
            # draft-cache row of d_K, so a fully-accepted round leaves the
            # draft pool position-synced; its sampled token is never used.
            (_, c_d, _, key), (drafts, draft_lg) = jax.lax.scan(
                draft_body, (toks, c_d, pos, key), None, length=kk + 1)

            ver_in = jnp.concatenate([toks[:, None], drafts[:kk].T], axis=1)
            logits_t, c_t = self.model.decode_paged(p_t, ver_in, c_t, pos,
                                                    tables)
            out, n_emit, key = _accept(logits_t.astype(jnp.float32),
                                       draft_lg[:kk], drafts[:kk],
                                       k_eligible, temps, key)
        return out, n_emit, c_t, c_d

    # -- host side ---------------------------------------------------------

    def reset_slot(self, slot: int) -> None:
        """Fresh adaptive-K state for a newly admitted request."""
        self._slots.pop(slot, None)

    def _slot_state(self, slot: int) -> SlotSpecState:
        st = self._slots.get(slot)
        if st is None:
            st = self._slots[slot] = SlotSpecState(k=self.k_max)
        return st

    def decode_round(self, tokens: np.ndarray, positions: np.ndarray,
                     temps: np.ndarray,
                     block_tables: Optional[np.ndarray] = None,
                     active: Optional[List[bool]] = None
                     ) -> Tuple[np.ndarray, np.ndarray]:
        """One speculative round for all slots; returns ``(grid, counts)``
        where ``grid`` is the (k_round+1, slots) emitted-token grid and
        ``counts`` the per-slot number of valid rows (1 + accepted drafts).
        The single host sync of the steady-state loop.

        ``k_round`` — the round's draft/verify width — is the max eligible
        K over the ACTIVE slots (per-slot adaptive state), so when every
        in-flight request's draft runs cold the round genuinely shrinks to
        fewer draft steps and verify columns, not just fewer accepted
        tokens.
        """
        if block_tables is None:
            raise ValueError("speculative decode needs block_tables")
        b = len(tokens)
        act = [True] * b if active is None else list(active)
        k_eligible = np.asarray(
            [self._slot_state(s).k if act[s] else 1 for s in range(b)],
            np.int32)
        k_round = max((int(k_eligible[s]) for s in range(b) if act[s]),
                      default=self.k_max)
        fn = self._get_spec_step(k_round)
        with self.tracer.span("runner.prepare"):
            self._key, sub = jax.random.split(self._key)
            args = (self.params, self.cache, self.draft_params,
                    self.draft_cache,
                    jnp.array(tokens, jnp.int32, copy=True),
                    jnp.array(positions, jnp.int32, copy=True),
                    jnp.array(block_tables, jnp.int32, copy=True),
                    jnp.array(k_eligible, jnp.int32, copy=True),
                    jnp.array(temps, jnp.float32, copy=True), sub)
        out, n_emit, self.cache, self.draft_cache = self._dispatch(
            "speculate", k_round, fn, args)
        self._count_kv_steps(k_round + 1, 1, self.draft_model)   # drafts
        self._count_kv_steps(1, k_round + 1)                     # verify
        with self.tracer.span("runner.wait"):
            out = np.asarray(out)
            counts = np.asarray(n_emit, dtype=np.int64).astype(np.int32)
        self.rounds += 1
        cfg = self.spec_cfg
        for s in range(b):
            if not act[s]:
                continue
            st = self._slot_state(s)
            acc = int(counts[s]) - 1
            # verification-yield counters: what the draft/verify loop
            # produced — a finishing request's budget may truncate the last
            # round's delivery below counts[s] (scheduler accounting)
            self.participations += 1
            self.drafted += int(k_eligible[s])
            self.accepted += acc
            self.emitted += int(counts[s])
            if cfg.adaptive:
                st.ewma = ((1 - cfg.ewma) * st.ewma
                           + cfg.ewma * acc / max(1, int(k_eligible[s])))
                if st.ewma < cfg.low:
                    st.k = max(cfg.k_min, st.k - 1)
                elif st.ewma > cfg.high:
                    st.k = min(self.k_max, st.k + 1)
        return out.T, counts

    def prefill_slot(self, slot: int, prompt: np.ndarray,
                     temperature: float = 0.0,
                     pages: Optional[np.ndarray] = None) -> int:
        """Admit into BOTH caches: the target prefill samples the first
        token as usual, then one jitted draft prefill writes the draft
        pool's rows for the same pages (scratch-redirected entries protect
        prefix-shared pages in both pools identically)."""
        tok = super().prefill_slot(slot, prompt, temperature, pages=pages)
        toks, n = self.padded_prompt(prompt)
        bucket = toks.shape[1]
        fn = self._get_draft_prefill(bucket)
        with self.tracer.span("runner.prepare"):
            args = (self.draft_params, jnp.asarray(toks), self.draft_cache,
                    jnp.asarray(pages, jnp.int32),
                    jnp.asarray(slot, jnp.int32), jnp.asarray(n, jnp.int32))
        self.draft_cache = self._dispatch("draft_prefill", bucket, fn, args)
        return tok

    def prefill_chunk(self, tokens: np.ndarray, positions: np.ndarray,
                      block_tables: np.ndarray, cols: np.ndarray,
                      temps: np.ndarray) -> np.ndarray:
        """Chunked admission advances BOTH pools: after the target's chunk,
        one jitted draft ``decode_paged`` writes the same rows into the
        draft cache (identical tokens/positions/tables), so a request that
        finishes chunked prefill enters the speculative rounds with the
        draft pool position-synced — exactly the bulk-admission state."""
        tok = super().prefill_chunk(tokens, positions, block_tables, cols,
                                    temps)
        width = tokens.shape[1]
        fn = self._get_draft_chunk(width)
        with self.tracer.span("runner.prepare"):
            args = (self.draft_params, self.draft_cache,
                    jnp.array(tokens, jnp.int32, copy=True),
                    jnp.array(positions, jnp.int32, copy=True),
                    jnp.array(block_tables, jnp.int32, copy=True))
        self.draft_cache = self._dispatch("draft_chunk", width, fn, args)
        self._count_kv_steps(1, width, self.draft_model)
        return tok

    def _get_draft_chunk(self, width: int):
        fn = self._draft_chunk_fns.get(width)
        if fn is None:
            def _fn(p, c, toks, pos, tables):
                with default_spec(self.spec):
                    _, c = self.draft_model.decode_paged(p, toks, c, pos,
                                                         tables)
                return c

            kw: Dict[str, Any] = {}
            if self.ctx is not None:
                kw["out_shardings"] = self.draft_cache_shardings
            fn = jax.jit(_fn, donate_argnums=(1,) if self.donate else (),
                         **kw)
            self._draft_chunk_fns[width] = fn
        return fn

    def _get_draft_prefill(self, bucket: int):
        fn = self._draft_prefill_fns.get(bucket)
        if fn is None:
            def _fn(p, toks, c, pages, slot, length):
                with default_spec(self.spec):
                    _, c = self.draft_model.prefill_paged(p, toks, c, pages,
                                                          slot, length)
                return c

            kw: Dict[str, Any] = {}
            if self.ctx is not None:
                kw["out_shardings"] = self.draft_cache_shardings
            fn = jax.jit(_fn, donate_argnums=(2,) if self.donate else (),
                         **kw)
            self._draft_prefill_fns[bucket] = fn
        return fn

    def spec_stats(self) -> Dict[str, Any]:
        """Lifetime speculation counters (surfaced via engine.stats()).

        ``acceptance`` measures draft quality (accepted / eligible drafts);
        ``emitted``/``tokens_per_round`` are VERIFICATION yield — the
        scheduler may deliver fewer on a request's final round (budget
        truncation).  ``tokens_per_round`` is PER SLOT-ROUND (1 + accepted
        drafts per participating slot, in [1, k+1]) so it reads as draft
        quality independent of how many slots were batched together.
        ``slot_k`` lists slots that have held a request.
        """
        return {
            "rounds": self.rounds,
            "drafted": self.drafted,
            "accepted": self.accepted,
            "acceptance": self.accepted / max(1, self.drafted),
            "tokens_per_round": self.emitted / max(1, self.participations),
            "slot_k": {s: st.k for s, st in sorted(self._slots.items())},
        }
