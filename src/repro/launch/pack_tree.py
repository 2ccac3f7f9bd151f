"""Whole-model conversion to the DeMM packed serving form.

``pack_tree(params)`` walks the param pytree and converts every sparse
linear (``{"w": ..., "sparsity": Static(cfg)}``) to a first-class
:class:`~repro.core.sparsity.PackedWeight` node, including the layer-stacked
scan case (leading stack dims are preserved on values/indices while
``dense_shape`` stays the per-layer 2-D shape).  ``layout`` selects the
packed format: ``"xwT"`` (default, the row-packed serving stream) or
``"block"`` (the two-level block format of ``core.sparsity.pack_block`` —
per row-block active-group lists gating the kernel's B DMAs); stacked block
weights share one ``a_max`` across the stack (``pack_block_stacked``) so
scan slicing works unchanged.  ``quantize="int8"`` additionally quantizes
every packed node (``repro.quant``): int8 values + traced scales + static
``qdtype`` aux, served by the w8a16 kernels.  ``pack_tree_shapes`` is the
eval_shape twin used by the dry-run; for shape-exact block dry-runs pass
``a_max`` explicitly (under tracing the active-group count cannot be read
from the data and defaults to all groups)."""

from __future__ import annotations

from typing import Optional

import jax

from repro.core import sparse_linear as sl
from repro.core.sparsity import LAYOUT_BLOCK, LAYOUT_XWT, PackedWeight


def _pack_sparse_linear(node, cfg, layout=LAYOUT_XWT, *, block_r=None,
                        a_max=None) -> PackedWeight:
    from repro.core.sparsity import pack_block_stacked

    w = node["w"]
    if layout == LAYOUT_BLOCK:
        # The block conversion prunes per-(row, group) itself; stacked
        # weights share one a_max so scan bodies slice the layer axis off
        # the packed children exactly as for xwT.
        return pack_block_stacked(w, cfg, block_r=block_r, a_max=a_max)
    if w.ndim == 2:
        return sl.pack_params(node, cfg)
    # layer-stacked (L, ..., O, K): pack every slice, restore the stack dims
    lead = w.shape[:-2]
    o, k = w.shape[-2], w.shape[-1]
    pw = jax.vmap(lambda wi: sl.pack_params({"w": wi}, cfg))(
        w.reshape(-1, o, k))
    return PackedWeight(
        pw.values.reshape(*lead, *pw.values.shape[1:]),
        pw.indices.reshape(*lead, *pw.indices.shape[1:]),
        cfg=cfg, dense_shape=(o, k), layout=pw.layout)


def pack_tree(params, layout: str = LAYOUT_XWT, *, block_r=None, a_max=None,
              quantize: Optional[str] = None, observer=None,
              granularity: str = "per_row"):
    """Convert every sparse linear in ``params`` to a PackedWeight.

    ``quantize`` (e.g. ``"int8"``) quantizes each packed node on the fly;
    ``observer`` is the optional calibration hook forwarded to
    ``repro.quant.quantize_packed`` (e.g. ``quant.activation_calibration``)
    and ``granularity`` the xwT scale unit (``per_row`` | ``per_group``).
    Already-packed nodes pass through (and are quantized if requested).
    """
    def q(pw: PackedWeight) -> PackedWeight:
        if quantize is None or pw.qdtype is not None:
            return pw
        from repro.quant import quantize_packed
        gran = "per_row" if pw.layout == LAYOUT_BLOCK else granularity
        return quantize_packed(pw, quantize, observer=observer,
                               granularity=gran)

    if isinstance(params, PackedWeight):
        return q(params)
    if isinstance(params, dict):
        if "values" in params and "shape" in params:
            raise ValueError(
                "legacy packed {values, indices, shape} dicts are no longer "
                "supported; re-pack the original weights with pack_tree to "
                "get PackedWeight nodes")
        if "w" in params:
            cfg = sl.node_sparsity(params)
            if cfg is not None:
                return q(_pack_sparse_linear(params, cfg, layout,
                                             block_r=block_r, a_max=a_max))
        return {k: pack_tree(v, layout, block_r=block_r, a_max=a_max,
                             quantize=quantize, observer=observer,
                             granularity=granularity)
                for k, v in params.items()}
    return params


def init_packed(model, key, **pack_kw):
    """``pack_tree(model.init(key), **pack_kw)`` as one jitted program.

    Decoder models pack each layer inside their layer-init loop, so the
    dense float32 tree never exists whole on the device: at stablelm_3b
    width it alone nearly fills a 16 GB chip, and packing it afterwards
    does not fit.  The result equals packing the dense init."""
    from functools import partial

    from repro.models.transformer import DecoderLM

    pack = partial(pack_tree, **pack_kw)

    def build(k):
        if isinstance(model, DecoderLM):
            return pack(model.init(k, layer_fn=pack))
        return pack(model.init(k))

    return jax.jit(build)(key)


def pack_tree_shapes(model, param_shapes, layout: str = LAYOUT_XWT, *,
                     block_r=None, a_max=None,
                     quantize: Optional[str] = None,
                     granularity: str = "per_row"):
    """ShapeDtypeStruct tree of the packed params (no allocation)."""
    return jax.eval_shape(
        lambda p: pack_tree(p, layout, block_r=block_r, a_max=a_max,
                            quantize=quantize, granularity=granularity),
        param_shapes)
