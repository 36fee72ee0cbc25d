"""Whole-pytree FORMS compression: ``compress_tree`` / ``decompress_tree``.

``compress_tree(params, spec)`` walks a model parameter pytree and replaces
every crossbar-mappable weight leaf with an actual
:class:`~repro.forms.linear.FormsLinearParams` (uint8 magnitudes + int8
fragment signs + f32 scales) — the deployment artifact the paper describes,
not a float fake-quant projection.  Scan-stacked (L, K, N) weights are
converted with a vmapped ``from_dense`` (fragments never cross the layer
axis); conv (kh, kw, cin, cout) kernels are viewed through the polarization
policy reshape and remember their original shape, so
``decompress_tree(compress_tree(p, spec))`` reproduces the projected weights
*exactly* (same values as projecting onto P then Q at the recorded scales).

The compressed tree is a first-class pytree: it jits, scans, shards and
checkpoints like the dense tree it replaces (``checkpoint/manager`` stores
the uint8 magnitudes verbatim), and ``models/layers.linear`` consumes its
leaves through the polarized-matmul kernel on the serving hot path.

A group of scan-stacked layers may come as a *layer stream*: a list of
zero-argument callables, the i-th making layer i's float tree (the group's
leaves without their leading layer axis).  ``compress_tree`` makes,
compresses and frees one layer at a time and writes its leaves into the
stacked compressed leaves, so a model whose float32 tree does not fit
next to its codes is set up holding one layer's float weights at most; the
codes equal those of compressing the stacked leaf.  :func:`stack_streams`
stacks streams as they are (no compression).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.fragments import conv_to_matrix, is_crossbar_weight
from repro.core.paths import path_str as _path_str
from repro.forms.linear import FormsLinearParams, from_dense, to_dense
from repro.forms.spec import FormsSpec

CompressedParams = Any  # a params pytree whose weight leaves are FormsLinearParams


@dataclasses.dataclass
class CompressReport:
    """What ``compress_tree`` did: per-leaf errors and storage accounting."""

    errors: Dict[str, float]          # path -> relative L2 projection error
    num_compressed: int = 0
    num_skipped: int = 0              # array leaves left dense (non-crossbar)
    bytes_dense: int = 0              # bytes of the leaves that were compressed
    bytes_compressed: int = 0         # bytes of their FORMS representation
    shardings: Dict[str, str] = dataclasses.field(default_factory=dict)
    # path -> mags PartitionSpec string, when compressed onto a mesh (ctx)
    bits: Dict[str, int] = dataclasses.field(default_factory=dict)
    # path -> magnitude bits (heterogeneous under a mixed-precision plan)

    @property
    def ratio(self) -> float:
        """Storage compression factor over the compressed leaves."""
        return self.bytes_dense / max(self.bytes_compressed, 1)

    @property
    def max_error(self) -> float:
        return max(self.errors.values()) if self.errors else 0.0

    def bits_histogram(self) -> Dict[int, int]:
        """bits -> number of compressed leaves stored at that width."""
        hist: Dict[int, int] = {}
        for b in self.bits.values():
            hist[b] = hist.get(b, 0) + 1
        return dict(sorted(hist.items()))

    def summary(self) -> str:
        hist = self.bits_histogram()
        bits_str = "/".join(f"{n}x{b}b" for b, n in hist.items()) or "-"
        return (f"{self.num_compressed} leaves compressed "
                f"({self.num_skipped} left dense, bits {bits_str}), "
                f"{self.bytes_dense / 1e6:.2f} MB -> "
                f"{self.bytes_compressed / 1e6:.2f} MB "
                f"({self.ratio:.2f}x), max rel-L2 err {self.max_error:.4f}")


def _is_forms_leaf(x) -> bool:
    return isinstance(x, FormsLinearParams)


def is_layer_stream(x) -> bool:
    """A list of zero-argument callables, each making one layer's tree."""
    return isinstance(x, list) and bool(x) and all(callable(f) for f in x)


@functools.partial(jax.jit, donate_argnums=0)
def _put_layer(stack: Any, layer: Any, i: jax.Array) -> Any:
    return jax.tree_util.tree_map(lambda s, x: s.at[i].set(x), stack, layer)


def _stack_stream(stream, convert: Callable[[int, Any], Any]) -> Any:
    """The stack of a layer stream's layers: layer i is made, passed through
    ``convert(i, tree)`` and written into row i of the stacked leaves (made
    at the first layer, updated in place), then dropped."""
    stack = None
    for i, make in enumerate(stream):
        layer = convert(i, make())
        if stack is None:
            stack = jax.tree_util.tree_map(
                lambda x: jnp.zeros((len(stream),) + x.shape, x.dtype), layer)
        stack = _put_layer(stack, layer, jnp.int32(i))
        del layer
    return stack


def stack_streams(params: Any) -> Any:
    """``params`` with every layer stream replaced by its stacked layers."""
    flat, treedef = jax.tree_util.tree_flatten(params, is_leaf=is_layer_stream)
    return jax.tree_util.tree_unflatten(
        treedef, [_stack_stream(x, lambda i, t: t) if is_layer_stream(x)
                  else x for x in flat])


# rank-4 leaves with these final path segments are scan-stacked expert
# tensors (L, E, in, out) — one crossbar matrix per (layer, expert) — not
# conv kernels (models/moe.py naming)
EXPERT_WEIGHT_NAMES = ("w_gate", "w_up", "w_down")


def spec_for_path(plan: Optional[Dict[str, FormsSpec]], pstr: str,
                  default: Optional[FormsSpec] = None) -> FormsSpec:
    """Resolve the spec of the leaf at ``pstr`` under a per-leaf plan.

    Lookup is by exact path, then by whole-segment suffix (a plan keyed
    ``"attn/wq"`` matches ``"blocks/attn/wq"``).  The failure modes are
    loud by design — a per-leaf override must never silently fall back to
    the global spec:

    * a suffix that matches more than one plan entry raises (ambiguous);
    * no match and no ``default`` raises ``KeyError`` naming the leaf and
      the plan's keys (a plan used without a global spec must be total).

    ``compress_tree`` additionally rejects plan entries that matched NO
    compressed leaf, so a typo'd path fails the compression instead of
    quietly serving the global spec.
    """
    if plan:
        if pstr in plan:
            return plan[pstr]
        hits = [key for key in plan if pstr.endswith("/" + key)]
        if len(hits) > 1:
            raise ValueError(
                f"plan entries {sorted(hits)} all match leaf {pstr!r} — "
                f"disambiguate with fuller paths (e.g. the exact "
                f"'{pstr}')")
        if hits:
            return plan[hits[0]]
    if default is None:
        raise KeyError(
            f"no spec for leaf {pstr!r}: not covered by the plan "
            f"(keys: {sorted(plan or {})}) and no global default given")
    return default


def _check_plan_covered(plan: Dict[str, FormsSpec],
                        compressed: Dict[str, Any]) -> None:
    """Every plan entry must have matched at least one compressed leaf."""
    unmatched = [key for key in plan
                 if key not in compressed
                 and not any(p.endswith("/" + key) for p in compressed)]
    if unmatched:
        raise ValueError(
            f"plan entries {sorted(unmatched)} matched no compressed leaf — "
            f"per-leaf overrides never fall back silently.  Compressed "
            f"leaves: {sorted(compressed)}")


@functools.partial(jax.jit, static_argnames=("name", "spec"))
def _compress_leaf(leaf: jax.Array, name: str, spec: FormsSpec
                   ) -> Tuple[FormsLinearParams, jax.Array]:
    """Convert one 2-D / scan-stacked 3-D / conv or expert 4-D weight leaf;
    returns ``(params, relative L2 error of the reconstruction)``.

    Jitted so XLA fuses the projection, quantization and error passes: run
    op by op, a full-width stacked leaf holds several f32 temporaries of its
    own size at once, which is what decides whether compressing a model
    fits next to its dense tree on one device.
    """
    if leaf.ndim == 3:       # scan-stacked (L, in, out): convert per layer
        fp, _ = jax.vmap(lambda w: from_dense(w, spec))(leaf)
    elif leaf.ndim == 4 and name in EXPERT_WEIGHT_NAMES:
        # stacked experts (L, E, in, out): per-(layer, expert) conversion
        fp, _ = jax.vmap(jax.vmap(lambda w: from_dense(w, spec)))(leaf)
    elif leaf.ndim == 4:     # conv (kh, kw, cin, cout): policy reshape
        fp, _ = from_dense(conv_to_matrix(leaf, spec.policy), spec)
        fp = dataclasses.replace(fp, orig_shape=tuple(leaf.shape))
    else:
        fp, _ = from_dense(leaf, spec)
    fp = dataclasses.replace(fp, out_dtype=str(leaf.dtype))
    err = (jnp.linalg.norm(to_dense(fp) - leaf)
           / jnp.maximum(jnp.linalg.norm(leaf), 1e-12))
    return fp, err


def compress_tree(
    params: Any,
    spec: Optional[FormsSpec] = FormsSpec(),
    predicate: Callable[[str, Tuple[int, ...]], bool] = is_crossbar_weight,
    ctx: Optional[Any] = None,
    plan: Optional[Dict[str, FormsSpec]] = None,
) -> Tuple[CompressedParams, CompressReport]:
    """Compress every crossbar-mappable weight of a params pytree.

    Returns ``(compressed, report)``.  ``compressed`` has the same tree
    structure with weight leaves replaced by ``FormsLinearParams``; all other
    leaves pass through untouched.  Already-compressed leaves are left alone,
    so the function is idempotent.  ``predicate(path, shape)`` selects the
    leaves to compress (default: the shared crossbar-weight heuristic).
    A layer stream (module docstring) becomes its stacked layers, made and
    compressed one layer at a time.

    ``plan`` (a ``{path: FormsSpec}`` map, e.g. from
    ``forms.autobits.plan_auto_bits``) overrides the spec per leaf — the
    heterogeneous mixed-precision tree.  Lookup follows
    :func:`spec_for_path` (exact path, then unambiguous suffix); entries
    that match no compressed leaf raise, so a typo'd override can never
    silently fall back to the global ``spec``.  Per-leaf bit-widths land in
    ``report.bits`` and in each leaf's ``bits`` metadata, which
    ``to_dense``/``apply`` and the checkpoint round-trip treat as
    authoritative.

    ``ctx`` (a ``distributed.sharding.ParallelContext``) places every
    compressed leaf straight onto its mesh sharding — mags/signs/scale
    co-sharded along N, K sharded only at whole-fragment granularity
    (``spec.k_shard_unit``, per leaf when the plan varies ``m``) — and
    records the chosen specs in ``report.shardings``.  Dense (skipped)
    leaves are left where they are; use :func:`shard_tree` to place the
    whole tree.
    """
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        params, is_leaf=lambda x: _is_forms_leaf(x) or is_layer_stream(x))
    report = CompressReport(errors={})
    compressed: Dict[str, Any] = {}

    def place(pstr: str, fp: FormsLinearParams) -> FormsLinearParams:
        if ctx is not None:
            fp = _place_forms_leaf(pstr, fp, ctx)
            report.shardings[pstr] = str(fp.mags.sharding.spec)
        compressed[pstr] = fp
        return fp

    def convert(pstr: str, leaf: Any, lead: Tuple[int, ...] = (),
                first: bool = True) -> Any:
        """One leaf compressed (or passed through) and accounted.  A layer
        of a stream passes its stack's leading axis as ``lead``, so the
        predicate sees the stacked shape; its error is the largest over the
        layers, it counts once (``first``) and is placed once stacked."""
        if (_is_forms_leaf(leaf) or not hasattr(leaf, "ndim")
                or not predicate(pstr, lead + tuple(leaf.shape))):
            if _is_forms_leaf(leaf):
                # idempotent pass-through still counts toward plan coverage
                compressed[pstr] = leaf
                report.bits[pstr] = leaf.bits
            elif hasattr(leaf, "ndim") and first:
                report.num_skipped += 1
            return leaf
        leaf_spec = spec_for_path(plan, pstr, spec)
        fp, err = _compress_leaf(leaf, pstr.rsplit("/", 1)[-1], leaf_spec)
        report.errors[pstr] = max(float(err), report.errors.get(pstr, 0.0))
        report.bits[pstr] = leaf_spec.bits
        report.num_compressed += int(first)
        report.bytes_dense += leaf.size * leaf.dtype.itemsize
        report.bytes_compressed += (fp.mags.size * fp.mags.dtype.itemsize
                                    + fp.signs.size * fp.signs.dtype.itemsize
                                    + fp.scale.size * fp.scale.dtype.itemsize)
        return fp if lead else place(pstr, fp)

    def convert_layer(pstr: str, n: int, i: int, layer: Any) -> Any:
        lflat, ltree = jax.tree_util.tree_flatten_with_path(layer)
        return jax.tree_util.tree_unflatten(ltree, [
            convert(f"{pstr}/{_path_str(p)}", x, (n,), i == 0)
            for p, x in lflat])

    new_leaves = []
    for path, leaf in flat:
        pstr = _path_str(path)
        if not is_layer_stream(leaf):
            new_leaves.append(convert(pstr, leaf))
            continue
        stack = _stack_stream(leaf, functools.partial(convert_layer, pstr,
                                                      len(leaf)))
        sflat, stree = jax.tree_util.tree_flatten_with_path(
            stack, is_leaf=_is_forms_leaf)
        new_leaves.append(jax.tree_util.tree_unflatten(stree, [
            place(f"{pstr}/{_path_str(p)}", x) if _is_forms_leaf(x) else x
            for p, x in sflat]))
    if plan:
        _check_plan_covered(plan, compressed)
    return jax.tree_util.tree_unflatten(treedef, new_leaves), report


def decompress_tree(params: CompressedParams, validate: bool = True) -> Any:
    """Exact inverse of :func:`compress_tree`.

    Replaces every ``FormsLinearParams`` leaf with its dense reconstruction
    (original shape and dtype); all other leaves pass through untouched.  The
    result equals the dense tree projected onto the polarized+quantized sets.
    ``validate=True`` first checks the co-sharding invariants of any
    mesh-committed leaves (:func:`validate_tree_sharding`) — reconstructing
    from a sign plane that shards differently from its magnitudes would
    silently apply wrong signs.
    """
    if validate:
        validate_tree_sharding(params)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        params, is_leaf=_is_forms_leaf)
    new_leaves = [to_dense(leaf) if _is_forms_leaf(leaf) else leaf
                  for _, leaf in flat]
    return jax.tree_util.tree_unflatten(treedef, new_leaves)


def compressed_paths(params: CompressedParams) -> Dict[str, FormsLinearParams]:
    """Map path -> FormsLinearParams for every compressed leaf (inspection)."""
    flat, _ = jax.tree_util.tree_flatten_with_path(
        params, is_leaf=_is_forms_leaf)
    return {_path_str(p): l for p, l in flat if _is_forms_leaf(l)}


# ---------------------------------------------------------------------------
# Mesh sharding of compressed trees
# ---------------------------------------------------------------------------
# distributed.sharding is imported lazily: it imports forms.linear at module
# level, so a module-level import here would be circular.

def _scanned(pstr: str) -> bool:
    from repro.distributed.sharding import SCANNED_PREFIXES
    return any(seg in pstr.split("/") for seg in SCANNED_PREFIXES)


def _place_forms_leaf(pstr: str, fp: FormsLinearParams, ctx: Any
                      ) -> FormsLinearParams:
    from repro.distributed.sharding import forms_leaf_shardings
    sh = forms_leaf_shardings(pstr, fp, ctx, scanned=_scanned(pstr),
                              fsdp=False)
    return jax.tree_util.tree_map(jax.device_put, fp, sh)


def shard_tree(params: CompressedParams, ctx: Any,
               fsdp: bool = False) -> CompressedParams:
    """Place a (possibly compressed) params pytree onto the mesh of ``ctx``.

    Compressed leaves get the co-sharded (mags, signs, scale) trio; dense
    leaves follow the standard naming rules.  ``fsdp=False`` by default —
    serving wants tensor-parallel weights replicated over the data axes, not
    ZeRO-3 gathers in the decode loop.
    """
    from repro.distributed.sharding import params_shardings, reshard_state
    return reshard_state(params, params_shardings(params, ctx, fsdp=fsdp))


def tree_sharding_specs(params: CompressedParams) -> Dict[str, Any]:
    """path -> ``mags`` PartitionSpec for every mesh-committed compressed
    leaf (inspection / test assertions via ``.sharding``)."""
    out = {}
    for pstr, fp in compressed_paths(params).items():
        sh = getattr(fp.mags, "sharding", None)
        if sh is not None and hasattr(sh, "spec"):
            out[pstr] = sh.spec
    return out


def _padded_spec(sharding: Any, ndim: int) -> Tuple[Any, ...]:
    spec = tuple(getattr(sharding, "spec", ()) or ())
    return spec + (None,) * (ndim - len(spec))


def _axis_shards(sharding: Any, entry: Any) -> int:
    if entry is None:
        return 1
    names = entry if isinstance(entry, (tuple, list)) else (entry,)
    size = 1
    for a in names:
        size *= dict(sharding.mesh.shape)[a]
    return size


def validate_tree_sharding(params: CompressedParams) -> Dict[str, Any]:
    """Validate the co-sharding invariants of every compressed leaf.

    For each mesh-committed ``FormsLinearParams`` leaf, checks that

    * mags and signs shard their K/fragment axis identically, and the K
      shard holds a whole number of fragments (multiple of ``m``);
    * mags, signs and scale carry the same N (output-column) entry;
    * the scale row axis is replicated.

    Raises ``ValueError`` naming the offending path; returns
    path -> mags PartitionSpec for the leaves checked.  Leaves whose arrays
    are not committed to a mesh (no ``NamedSharding``) are skipped.
    """
    checked = {}
    for pstr, fp in compressed_paths(params).items():
        shs = [getattr(a, "sharding", None)
               for a in (fp.mags, fp.signs, fp.scale)]
        if any(s is None or not hasattr(s, "spec") for s in shs):
            continue
        mags_sh, signs_sh, scale_sh = shs
        mspec = _padded_spec(mags_sh, fp.mags.ndim)
        sspec = _padded_spec(signs_sh, fp.signs.ndim)
        cspec = _padded_spec(scale_sh, fp.scale.ndim)
        if mspec[-1] != sspec[-1] or mspec[-1] != cspec[-1]:
            raise ValueError(
                f"{pstr}: N (output-column) axis must co-shard across "
                f"mags/signs/scale, got {mspec[-1]!r}/{sspec[-1]!r}/"
                f"{cspec[-1]!r} — per-column scales and fragment signs are "
                f"state of the same columns as the magnitudes")
        if mspec[-2] != sspec[-2]:
            raise ValueError(
                f"{pstr}: sign fragment axis must shard exactly like the "
                f"mags K axis (got {sspec[-2]!r} vs {mspec[-2]!r}); a "
                f"fragment's sign multiplies all {fp.m} of its rows")
        if cspec[-2] is not None:
            raise ValueError(
                f"{pstr}: scale row axis must be replicated, got "
                f"{cspec[-2]!r}")
        kshards = _axis_shards(mags_sh, mspec[-2])
        kp = fp.mags.shape[-2]
        if kshards > 1 and (kp % kshards != 0
                            or (kp // kshards) % fp.m != 0):
            raise ValueError(
                f"{pstr}: K={kp} sharded {kshards}-way gives "
                f"{kp / kshards:g}-row shards, not a multiple of the "
                f"fragment size m={fp.m} — sign blocks would straddle "
                f"devices.  Re-shard with shards*m dividing K.")
        checked[pstr] = mags_sh.spec
    return checked
