"""repro.forms — the unified FORMS compression API.

One spec, one compressed representation, one pipeline:

* :class:`FormsSpec` — the single frozen descriptor (fragment geometry +
  quantization grid + sign rule + bit-serial and backend/tiling hints).
* :class:`FormsLinearParams` — the compressed weight pytree (uint8 magnitude
  codes, int8 fragment signs, f32 scales) with :func:`from_dense` /
  :func:`to_dense` / :func:`apply` / :func:`apply_simulated`.
* :func:`compress_tree` / :func:`decompress_tree` — whole-model compression
  producing pytrees whose crossbar leaves are real ``FormsLinearParams``,
  consumed directly by ``models/layers.linear`` and the serving engine.
  ``compress_tree(plan={path: FormsSpec})`` compresses heterogeneously —
  per-leaf overrides resolved by :func:`spec_for_path` — and
  :mod:`repro.forms.autobits` derives such plans automatically from a
  Fisher-diagonal sensitivity sweep (``serve --auto-bits``).

The PR-1 deprecation shims (``repro.core.forms_layer``,
``repro.serving.engine.forms_compress_params``) have been REMOVED; this
package is the only compression surface (see DESIGN.md §9 for the old ->
new mapping).
"""
from repro.forms.autobits import (AutoBitsConfig, AutoBitsPlan,
                                  measure_sensitivity, plan_auto_bits,
                                  plan_draft_bits, plan_from_meta,
                                  plan_to_meta)
from repro.forms.linear import (FormsLinearParams, apply, apply_simulated,
                                default_spec, from_dense, sparsity_stats,
                                to_dense)
from repro.forms.spec import FormsSpec
from repro.forms.tree import (CompressedParams, CompressReport,
                              compress_tree, compressed_paths,
                              decompress_tree, shard_tree, spec_for_path,
                              stack_streams, tree_sharding_specs,
                              validate_tree_sharding)

__all__ = [
    "FormsSpec", "FormsLinearParams", "from_dense", "to_dense", "apply",
    "apply_simulated", "default_spec", "sparsity_stats", "compress_tree",
    "decompress_tree",
    "compressed_paths", "CompressReport", "CompressedParams",
    "shard_tree", "tree_sharding_specs", "validate_tree_sharding",
    "spec_for_path", "stack_streams",
    "AutoBitsConfig", "AutoBitsPlan", "measure_sensitivity",
    "plan_auto_bits", "plan_draft_bits", "plan_to_meta", "plan_from_meta",
]
