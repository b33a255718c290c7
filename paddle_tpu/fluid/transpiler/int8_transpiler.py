"""Weight-only int8 inference transpiler.

The reference quantizes inference graphs through its analysis pipeline
(ref: inference/analysis/, fake_quantize/fake_dequantize ops, QAT flow);
the fp16 analogue is contrib/float16/float16_transpiler.py, which rewrites
weights in the scope and patches the program.  This is the TPU-native
int8 counterpart, specialized to the part that pays off under XLA:

 - weights of matmul/conv ops are stored int8 (4x less HBM, the real
   bottleneck on inference), with a per-output-channel abs-max scale;
 - a ``dequantize_weight`` op materializes the float weight right at the
   consuming op; XLA fuses the cast+scale into the matmul/conv read, so
   activations and accumulation stay float — "weight-only" quantization,
   the standard accuracy-safe recipe (<1%% drop without calibration data).

Scales come from the weights themselves (per-channel abs-max): weight-only
quantization needs no calibration data or QAT observers — the fake_quantize
ops (ops/quant_ops.py) remain the training-time QAT surface, and a QAT'd
model's weights quantize here losslessly since training already pinned them
to the quantization grid.
"""

from __future__ import annotations

import numpy as np

from ..framework import NAME_SCOPE_ATTR, name_scope_at

# op type -> (weight input slot, per-output-channel axis of the weight)
_QUANT_TARGETS = {
    "mul": ("Y", 1),        # [in, out]
    "conv2d": ("Filter", 0),  # [out_c, in_c, kh, kw]
    # embeddings: per-row scales; the dominant weight of decode programs.
    # XLA fuses gather+dequant, so int8 rows stream from HBM.
    "lookup_table": ("W", 0),
}


class Int8WeightTranspiler:
    """Rewrite an INFERENCE program + scope for weight-only int8."""

    def __init__(self, min_elements: int = 64):
        # tiny weights (biases folded into mul, 1x1 vectors) aren't worth
        # the dequant op; skip anything smaller than min_elements
        self.min_elements = min_elements

    def transpile(self, program, place=None, scope=None):
        from ..executor import global_scope
        from ..framework import Parameter

        scope = scope or global_scope()
        gb = program.global_block()
        # pass 1 — collect every consuming site across ALL blocks before
        # touching the scope: a shared weight (tied embedding, reused
        # projection) may be consumed in several blocks, and _quantize
        # drops the fp32 copy, so per-block collect-and-rewrite would
        # miss later consumers
        sites = []  # (block, op index, op, slot, wname)
        axes = {}   # wname -> quant axis (consistent per target table)
        weights = {}
        for block in program.blocks:
            for i, op in enumerate(block.ops):
                target = _QUANT_TARGETS.get(op.type)
                if target is None:
                    continue
                slot, axis = target
                names = op.inputs.get(slot) or []
                if len(names) != 1:
                    continue
                wname = names[0]
                if wname not in weights:
                    if not gb._has_var_recursive(wname) or \
                            not isinstance(gb._var_recursive(wname),
                                           Parameter):
                        continue
                    w = scope.get(wname, None)
                    if w is None:
                        continue
                    w = np.asarray(w)
                    if w.size < self.min_elements or \
                            not np.issubdtype(w.dtype, np.floating):
                        continue
                    weights[wname] = w
                    axes[wname] = axis
                elif axes[wname] != axis:
                    continue  # same weight, incompatible channel axis
                sites.append((block, i, op, slot, wname))

        # pass 2 — quantize each weight ONCE and rewrite every consumer
        for wname, w in weights.items():
            self._quantize(gb, scope, wname, w, axes[wname])
        for _, _, op, slot, wname in sites:
            op.inputs[slot] = [wname + "@DEQ"]
        # one dequantize_weight per (block, weight), before its first
        # consumer there (shared by all consumers in that block); insert
        # back-to-front so original indices stay valid
        for block in program.blocks:
            firsts = {}  # wname -> first consumer index in this block
            for b, i, _, _, wname in sites:
                if b is block:
                    firsts[wname] = min(firsts.get(wname, i), i)
            for wname, i in sorted(firsts.items(), key=lambda t: -t[1]):
                # under the name scope of the consumer it is made for
                with name_scope_at(block.ops[i].attr(NAME_SCOPE_ATTR, "")):
                    block._insert_op(
                        i, type="dequantize_weight",
                        inputs={"X": [wname + "@INT8"],
                                "Scale": [wname + "@SCALE"]},
                        outputs={"Out": [wname + "@DEQ"]},
                        attrs={"quant_axis": axes[wname]})
            if firsts:
                self._patch_owner_ops(program, block, list(firsts))
        return list(weights)

    def _patch_owner_ops(self, program, block, wnames):
        """Sub-block weights (e.g. the step block of a jit_beam_search op,
        or a While body) are pulled into scope through the OWNING op's X
        input list, which was computed at build time against the float
        weights.  Swap the quantized names in so the executor feeds the
        int8 weight + scale instead of the (now dropped) float copy."""
        owner = None
        for b in program.blocks:
            for op in b.ops:
                if op.attr("sub_block") == block.idx:
                    owner = op
                    break
        if owner is None or "X" not in owner.inputs:
            return
        x = [n for n in owner.inputs["X"] if n not in wnames]
        for w in wnames:
            x.extend([w + "@INT8", w + "@SCALE"])
        owner.inputs["X"] = x

    def _quantize(self, block, scope, wname, w, axis):
        """Store int8 weight + per-channel scale in scope/block; drop the
        float original from the scope (that is the memory win)."""
        gb = block.program.global_block()
        reduce_axes = tuple(d for d in range(w.ndim) if d != axis)
        scale = np.abs(w).max(axis=reduce_axes).astype(np.float32)
        scale = np.where(scale > 0, scale, 1.0)
        shape = [1] * w.ndim
        shape[axis] = -1
        q = np.clip(np.round(w / scale.reshape(shape) * 127.0),
                    -127, 127).astype(np.int8)

        wq_name, sc_name = wname + "@INT8", wname + "@SCALE"
        gb.create_var(name=wq_name, shape=tuple(q.shape), dtype="int8",
                      persistable=True)
        gb.create_var(name=sc_name, shape=tuple(scale.shape),
                      dtype="float32", persistable=True)
        dq_name = wname + "@DEQ"
        gb.create_var(name=dq_name, shape=tuple(w.shape), dtype="float32",
                      persistable=False)
        scope.set(wq_name, q)
        scope.set(sc_name, scale)
        scope._values.pop(wname, None)  # the float copy is the memory win
        return dq_name
