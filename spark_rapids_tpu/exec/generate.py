"""Generate (explode / posexplode) — lateral view over array columns.

Reference: sql-plugin/.../rapids/GpuGenerateExec.scala (explode,
posexplode, outer variants; lazy-array optimization). The cudf design
gathers via an offsets column; the TPU layout is already rectangular
(``data[cap, max_elems]`` + ``lengths``), so explode is a *reshape*:

1. broadcast every required child column across the element axis
   → ``[cap, me]`` and flatten to ``[cap*me]``,
2. build the element keep-mask (slot < length; for OUTER, slot 0 of an
   empty/null array also survives, with a null element),
3. stable-compact — the same cumsum-scatter primitive filters use.

The whole thing is one fused XLA program per batch; no per-row host work.
Output capacity is the static bound ``cap * me`` (the planner gates
oversized budgets via TypeSig, like the reference's batch-size splitting
in GpuGenerateExec.scala's fixUpBatches).
"""

from __future__ import annotations

from typing import Iterator, Optional

import jax
import jax.numpy as jnp

from .. import types as T
from ..batch import ColumnarBatch, DeviceColumn, Field, Schema
from ..expressions.base import EvalContext, Expression
from ..types import TypeKind
from .base import UnaryExec
from .common import KernelPrograms, compact


class GenerateExec(UnaryExec):
    """explode/posexplode over one array-typed generator expression.

    ``outer=True`` keeps rows whose array is null/empty, emitting one row
    with a null element (Spark's EXPLODE_OUTER / LATERAL VIEW OUTER).
    ``pos=True`` prepends the element position column (posexplode).
    """

    def __init__(self, generator: Expression, child: "Exec",
                 outer: bool = False, pos: bool = False,
                 elem_name: str = "col", pos_name: str = "pos",
                 value_name: str = "value",
                 ctx: Optional[EvalContext] = None):
        super().__init__(child, ctx)
        self.generator = generator.bind(child.output_schema)
        self.outer = outer
        self.pos = pos
        gt = self.generator.dtype
        if gt.kind not in (TypeKind.ARRAY, TypeKind.MAP):
            raise TypeError(f"explode expects an array or map, got {gt}")
        self.is_map = gt.kind is TypeKind.MAP
        fields = list(child.output_schema.fields)
        if pos:
            fields.append(Field(pos_name, T.INT32, outer))
        if self.is_map:
            fields.append(Field(elem_name, gt.children[0], outer))
            fields.append(Field(value_name, gt.children[1], outer))
        else:
            fields.append(Field(elem_name, gt.children[0], outer))
        self._schema = Schema(fields)

        def kernel(self, batch):
            from .basic import _sum_errors
            kctx = EvalContext(self.ctx.ansi, {})
            return self._explode_kernel(batch, kctx), _sum_errors(kctx)

        self._kernel = KernelPrograms(
            self, ("generator", "outer", "pos", "is_map")).jit(
                "explode", kernel)

    @property
    def output_schema(self) -> Schema:
        return self._schema

    def _explode_kernel(self, batch: ColumnarBatch,
                        ctx: EvalContext) -> ColumnarBatch:
        # flatten_repeat rebuilds carried columns lane by lane and has no
        # dictionary slot — decode dict strings first (repeat-then-decode
        # and decode-then-repeat commute)
        from ..dictenc import decode_batch
        batch = decode_batch(batch)
        arr = self.generator.eval(batch, ctx)
        cap, me = arr.data.shape[:2]     # array<string> data is 3D
        out_cap = cap * me
        slot = jnp.arange(me, dtype=jnp.int32)[None, :]        # [1, me]
        row_live = batch.row_mask()
        has_elem = arr.validity & (arr.lengths > 0)
        keep = (slot < arr.lengths[:, None]) & arr.validity[:, None]
        elem_valid = keep
        if self.outer:
            pad_row = (slot == 0) & (~has_elem)[:, None]
            keep = keep | pad_row
        keep = keep & row_live[:, None]

        def flatten_repeat(col: DeviceColumn) -> DeviceColumn:
            data = jnp.broadcast_to(col.data[:, None], (cap, me) +
                                    col.data.shape[1:]).reshape(
                (out_cap,) + col.data.shape[1:])
            validity = jnp.broadcast_to(col.validity[:, None],
                                        (cap, me)).reshape(out_cap)
            lengths = None
            if col.lengths is not None:
                lengths = jnp.broadcast_to(col.lengths[:, None],
                                           (cap, me)).reshape(out_cap)
            data2 = None
            if col.data2 is not None:
                data2 = jnp.broadcast_to(col.data2[:, None], (cap, me) +
                                         col.data2.shape[1:]).reshape(
                    (out_cap,) + col.data2.shape[1:])
            return DeviceColumn(data, validity, lengths, col.dtype, data2)

        cols = [flatten_repeat(c) for c in batch.columns]
        if self.pos:
            # Spark posexplode_outer: pad rows carry NULL pos
            pos_data = jnp.broadcast_to(slot, (cap, me)).reshape(out_cap)
            cols.append(DeviceColumn(pos_data, elem_valid.reshape(out_cap),
                                     None, T.INT32))
        gt = self.generator.dtype
        if not self.is_map and arr.data.ndim == 3:
            # array<string>: elements flatten to a [cap*me, max_len] byte
            # matrix with per-element lengths from data2
            el = jnp.where(elem_valid.reshape(out_cap)[:, None],
                           arr.data.reshape(out_cap, arr.data.shape[2]), 0)
            el_lens = jnp.where(elem_valid.reshape(out_cap),
                                arr.data2.reshape(out_cap), 0)
            cols.append(DeviceColumn(el, elem_valid.reshape(out_cap),
                                     el_lens, gt.children[0]))
        else:
            cols.append(DeviceColumn(arr.data.reshape(out_cap),
                                     elem_valid.reshape(out_cap), None,
                                     gt.children[0]))
        if self.is_map:
            cols.append(DeviceColumn(arr.data2.reshape(out_cap),
                                     elem_valid.reshape(out_cap), None,
                                     gt.children[1]))
        # every flat slot is "live" (compact ANDs with row_mask; the real
        # row selection is the keep mask)
        flat = ColumnarBatch(tuple(cols), jnp.asarray(out_cap, jnp.int32))
        return compact(flat, keep.reshape(out_cap))

    def do_execute_partition(self, p: int) -> Iterator[ColumnarBatch]:
        from .basic import _raise_ansi
        for batch in self.child.execute_partition(p):
            out, errs = self._kernel(batch)
            _raise_ansi(errs)
            yield out
